#!/usr/bin/env python3
"""Compile the two ENGINE programs of a configuration whose layers keep a
Kimi-Delta-Attention state (a 128 x 128 float32 matrix a head whose rows
decay each by a factor of their own, and the rows of a short convolution:
two per-slot leaves a layer) beside per-head K/V pages of 8 KV heads under 64
gated query heads, every layer's experts a HELD share of its router's, for a
DESCRIBED v5e chip, without a chip: ``programs.build_fused_sample_step`` (``max_seqs + prefill_chunk``
rows) and ``programs.build_decode_block`` (``decode_block_size`` ticks of
``max_seqs`` rows), over the whole pool, as the engine builds them.  Prints
what the TPU compiler needs on the device, the kernel calls in each program
and the routes taken, and refuses a program in which one of the five
kernels fell to its XLA twin.

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_solar_v5e.py [config]

After ``serve_olmo_hybrid_v5e.py`` (not edited).  A script, never imported by a
test: it describes the topology at top level.  A compile that passes is not
a chip run.
"""
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import flops_solar
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.inference import paged as paged_lib
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.models.llama import count_params
from deepspeed_tpu.ops import (grouped_matmul, kda, paged_decode,
                               paged_prefill, routes)

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "solar-open2-250b-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
page, S = int(eng["page_size"]), int(eng["max_seqs"])
T = S + int(eng["prefill_chunk"])
page_bytes = programs.paged_kv_page_bytes(plain, mcfg, page, "none")
assert page_bytes == page * flops_solar.pool_bytes_per_token(config), (
    "flops_solar.py and the pool disagree on a token's stored bytes")
pages = int(eng["kv_pool_bytes"]) // page_bytes
pp = -(-int(eng["max_seq_len"]) // page)
model = model_cls(programs.serving_config(
    mcfg, max_seq_len=int(eng["max_seq_len"]), page_size=page,
    num_pages=pages, tensor_parallel=False, kv_cache_dtype="none"))


def on_chip(tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)


arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
i32 = lambda *shape: arr(jnp.int32, *shape)
f32 = lambda *shape: arr(jnp.float32, *shape)
boo = lambda *shape: arr(jnp.bool_, *shape)

params = on_chip(jax.eval_shape(
    plain.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"])
zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
cache = on_chip(jax.eval_shape(lambda: model.init(
    jax.random.PRNGKey(0), zeros(1, T), positions=zeros(1, T),
    ragged_meta={"kv_lens": zeros(S), "page_indices": zeros(S, pp),
                 "cu_q_lens": zeros(S + 1), "num_seqs": zeros(1),
                 "new_kv_dest": zeros(T)}))["cache"])
pools, states = paged_lib.split_cache(cache)
nbytes = lambda ls: sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in ls)
assert nbytes(states) >= S * flops_solar.state_bytes_per_seq(config), (
    "flops_solar.py counts more state than the cache holds")
key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)

fused = programs.build_fused_sample_step(
    model, sampled=False, sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(1, T), i32(1, T), i32(1, T), i32(S),
        i32(S, pp), i32(S + 1), i32(1), i32(T), i32(S), i32(S),
        i32(S))              # per-slot state: each packed sequence's slot
block = programs.build_decode_block(
    model, sampled=False, max_seqs=S, block=int(eng["decode_block_size"]),
    page_size=page, max_seq_len=int(eng["max_seq_len"]),
    sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(S), boo(S), i32(S), i32(S, pp), i32(S),
        boo(S), f32(S), i32(S), f32(S), i32(S), key)

gib = 2.0 ** 30
delta, attn = paged_lib.state_layers(mcfg), paged_lib.kv_layers(mcfg)
print({"config": name, "parameters": count_params(params),
       "pages": pages, "page_bytes": page_bytes,
       "bytes_per_token": page_bytes // page, "pool_gib": nbytes(pools) / gib,
       "state_gib": nbytes(states) / gib,
       "state_bytes_per_seq_stored": nbytes(states) // S,
       "state_bytes_per_seq_counted":
       flops_solar.state_bytes_per_seq(config),
       "pool_tokens": (pages - 1) * page})
# kernel -> calls in (the fused tick, the decode block): a tick's chunks take
# the scan and the chunk kernel, its one-row sequences and every row of a
# decode block the two one-token kernels; a held expert layer's three
# grouped matmuls are in BOTH branches of its ``cond`` (the room for a
# quarter of the copies, and for all of them)
layers = delta + attn
CALLS = {kda.SCAN_NAME: (delta, 0), kda.STEP_NAME: (delta, delta),
         paged_prefill.NAME: (attn, 0), paged_decode.NAME: (attn, attn),
         grouped_matmul.NAME: (6 * layers, 6 * layers)}
for which, (what, lowered) in enumerate((("fused_tick", fused),
                                         ("decode_block", block))):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    report = {
        "program": what,
        "argument_gib": mem.argument_size_in_bytes / gib,
        "temp_gib": mem.temp_size_in_bytes / gib,
        "total_gib": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes
                      + mem.temp_size_in_bytes) / gib,
        "pallas_kernels": text.count('custom_call_target="tpu_custom_call"'),
        **{n + "_calls": len(re.findall(rf"%{n}[.\d]* = ", text))
           for n in CALLS}}
    print(report)
    for n, counts in CALLS.items():
        assert report[n + "_calls"] == counts[which], (
            f"{what}: {report[n + '_calls']} calls of {n}, "
            f"{counts[which]} expected")
print({"routes": routes.taken()})
want = config["expected_routes"]
got = {k: v for k, v in routes.taken().items() if k in want}
assert got == want, (got, want)
