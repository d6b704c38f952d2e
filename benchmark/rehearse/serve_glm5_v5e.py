#!/usr/bin/env python3
"""Compile the two ENGINE programs of a configuration whose LATENT attention
reads the rows an indexer fed by the query latent chooses (a latent row and
an index key a token, two pool leaves) and whose expert layers hold a share
of their router's experts, for a DESCRIBED v5e chip, without a chip:
``programs.build_fused_sample_step`` (``max_seqs + prefill_chunk`` rows)
and ``programs.build_decode_block`` (``decode_block_size`` ticks of
``max_seqs`` rows), over the whole pool, as the engine builds them.  Prints
what the TPU compiler needs on the device, the kernel calls in each program
and the routes taken, and refuses a program in which one of the two chunk
kernels (``index_select``, which scores and chooses in one, and
``latent_select_chunk_attention``) or the one-row ``row_index_scores`` fell
to its XLA twin.

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_glm5_v5e.py [config]

Beside ``serve_keye_v5e.py`` (not edited).  A script, never imported by a
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

from benchmark import flops_glm5
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.inference import paged as paged_lib
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.models.llama import count_params
from deepspeed_tpu.ops import latent_select_attention as lsa
from deepspeed_tpu.ops import routes
from deepspeed_tpu.ops import token_select_attention as tsa

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "glm-5-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
page, S = int(eng["page_size"]), int(eng["max_seqs"])
T = S + int(eng["prefill_chunk"])
page_bytes = programs.paged_kv_page_bytes(plain, mcfg, page, "none")
assert page_bytes == page * flops_glm5.pool_bytes_per_token(config), (
    "flops_glm5.py and the pool disagree on a token's stored bytes")
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
assert not states, "this model keeps no per-slot state"
key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)

fused = programs.build_fused_sample_step(
    model, sampled=False, sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(1, T), i32(1, T), i32(1, T), i32(S),
        i32(S, pp), i32(S + 1), i32(1), i32(T), i32(S), i32(S))
block = programs.build_decode_block(
    model, sampled=False, max_seqs=S, block=int(eng["decode_block_size"]),
    page_size=page, max_seq_len=int(eng["max_seq_len"]),
    sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(S), boo(S), i32(S), i32(S, pp), i32(S),
        boo(S), f32(S), i32(S), f32(S), i32(S), key)

gib = 2.0 ** 30
layers = paged_lib.kv_layers(mcfg)
print({"config": name, "parameters": count_params(params),
       "pages": pages, "page_bytes": page_bytes,
       "bytes_per_token": page_bytes // page, "pool_gib": nbytes(pools) / gib,
       "state_gib": nbytes(states) / gib, "pool_tokens": (pages - 1) * page})
NAMES = (tsa.SELECT_NAME, lsa.CHUNK_NAME)      # a prompt chunk's, a layer
ROW = tsa.ROW_INDEX_NAME            # the one-row path's, in both programs
for what, lowered in (("fused_tick", fused), ("decode_block", block)):
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
           for n in NAMES + (ROW,)}}
    print(report)
    # a decode block's rows take the one-row path: no chunk kernel
    for n, count in {**{n: layers if what == "fused_tick" else 0
                        for n in NAMES}, ROW: layers}.items():
        assert report[n + "_calls"] == count, (
            f"{what}: {report[n + '_calls']} calls of {n}, {count} layers")
print({"routes": routes.taken()})
want = config["expected_routes"]
got = {k: v for k, v in routes.taken().items() if k in want}
assert got == want, (got, want)
