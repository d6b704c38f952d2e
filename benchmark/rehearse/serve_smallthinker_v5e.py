#!/usr/bin/env python3
"""Compile the two ENGINE programs of a configuration whose WINDOW layers and
FULL layers each keep a page pool behind a page table of their own, at FOUR KV
heads stored as four (28 query heads over 4 KV heads of 128, a query group of
seven, a token PAIR a sublane tile in both kernels; the window layers' pool
2,625 pages of six layers, the full layers' 4,129 of two), every layer a
router that reads the layer's input over 64 ReGLU experts of 768, all held,
for a DESCRIBED v5e chip, without a chip: ``programs.build_fused_sample_step``
(``max_seqs + prefill_chunk`` rows) and ``programs.build_decode_block``
(``decode_block_size`` ticks of ``max_seqs`` rows), over both pools, as the
engine builds them (``page_indices [2, S, pp]``, ``new_kv_dest [2, T]``).
Prints what the TPU compiler needs on the device, the kernel calls in each
program and the routes taken, and refuses a program in which one of the
three kernels fell to its XLA twin, or one that copies a pool.

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_smallthinker_v5e.py [config]

After ``serve_trinity_v5e.py`` (not edited).  A script, never imported by a
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

from benchmark import flops_smallthinker as flops
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.inference import paged as paged_lib
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.models.llama import count_params
from deepspeed_tpu.ops import (grouped_matmul, paged_decode, paged_prefill,
                               routes)

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "smallthinker-21b-a3b-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
page, S = int(eng["page_size"]), int(eng["max_seqs"])
T = S + int(eng["prefill_chunk"])
page_bytes = {kind: programs.paged_kv_page_bytes(
    plain, mcfg, page, "none", window=kind == flops.WINDOW)
    for kind in (flops.FULL, flops.WINDOW)}
for kind, got in page_bytes.items():
    assert got == page * flops.pool_bytes_per_token(config, kind), (
        "flops_smallthinker.py and the pools disagree on a token's stored bytes")
pages = int(eng["kv_pool_bytes"]) // page_bytes[flops.FULL]
own_pages = int(eng["window_pool_bytes"]) // page_bytes[flops.WINDOW]
pp = -(-int(eng["max_seq_len"]) // page)
bound = paged_lib.pages_bound(int(config["sliding_window_size"]), T, page)
assert own_pages >= S * bound + 1, "the window pool holds every slot's bound"
assert pages >= S * pp + 1, "the first pool holds every slot at its longest"
model = model_cls(programs.serving_config(
    mcfg, max_seq_len=int(eng["max_seq_len"]), page_size=page,
    num_pages=pages, tensor_parallel=False, kv_cache_dtype="none",
    window_num_pages=own_pages))


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
                 "window_page_indices": zeros(S, pp),
                 "cu_q_lens": zeros(S + 1), "num_seqs": zeros(1),
                 "new_kv_dest": zeros(T),
                 "window_new_kv_dest": zeros(T)}))["cache"])
first, own = paged_lib.split_cache(cache)[0], paged_lib.window_pool(cache)
nbytes = lambda ls: sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in ls)
assert nbytes(first) == int(eng["kv_pool_bytes"])
assert nbytes(own) == int(eng["window_pool_bytes"])
key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)

fused = programs.build_fused_sample_step(
    model, sampled=False, sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(1, T), i32(1, T), i32(1, T), i32(S),
        i32(2, S, pp), i32(S + 1), i32(1), i32(2, T), i32(S), i32(S))
block = programs.build_decode_block(
    model, sampled=False, max_seqs=S, block=int(eng["decode_block_size"]),
    page_size=page, max_seq_len=int(eng["max_seq_len"]),
    sample_base=jax.random.PRNGKey(0)).lower(
        params, cache, i32(S), i32(S), boo(S), i32(S), i32(2, S, pp), i32(S),
        boo(S), f32(S), i32(S), f32(S), i32(S), key)

gib = 2.0 ** 30
layers = int(mcfg.num_hidden_layers)
print({"config": name, "parameters": count_params(params),
       "pages": pages, "window_pages": own_pages, "page_bytes": page_bytes,
       "pool_gib": nbytes(first) / gib, "window_pool_gib": nbytes(own) / gib,
       "one_table_gib": (layers * nbytes(first)
                         / flops.layers(config, flops.FULL) / gib),
       "window_pages_bound_per_seq": bound})
# kernel -> calls in (the fused tick, the decode block): a tick's chunks take
# the chunk kernel, its one-row sequences and every row of a decode block the
# decode kernel, a layer of either kind; every layer's three grouped matmuls
CALLS = {paged_prefill.NAME: (layers, 0), paged_decode.NAME: (layers, layers),
         grouped_matmul.NAME: (3 * layers, 3 * layers)}
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
    # the kernels read each pool where it lies, a token pair a tile: a copy
    # of a pool would be gigabytes a call
    copied = [line[:160] for line in text.splitlines() if " copy(" in line
              and re.search(rf"bf16\[({pages}|{own_pages}),", line)]
    assert not copied, copied
print({"routes": routes.taken()})
want = config["expected_routes"]
got = {k: v for k, v in routes.taken().items() if k in want}
assert got == want, (got, want)
