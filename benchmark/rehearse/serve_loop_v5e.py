#!/usr/bin/env python3
"""Compile the two serving programs of a LOOPED configuration (layers run
``total_ut_steps`` times a token) for a DESCRIBED v5e chip, without a chip:
the engine's own fused SplitFuse tick and its decode block of
``decode_block_size`` ticks, through the public builders
(``deepspeed_tpu/inference/v2/programs.py``), over the whole paged KV pool
with ``total_ut_steps`` slots a page.  Prints what the TPU compiler needs on
the device, how long tracing and lowering (``lower_s``) and each compile
took, how often each attention kernel
appears in the program (the blocks must be there ONCE, inside the pass
loop, not once a pass) and the largest temporaries (a copy of a layer's
pool would show there).

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_loop_v5e.py [config] [pages]

Beside ``serve_v5e.py`` and ``serve_moe_v5e.py`` (which rebuild a tick from
the model's paged config and are not edited).  ``pages`` overrides the
pool's size, to find the largest that fits.  A script, never imported by a
test: it describes the topology at top level.  A compile that passes is not
a chip run.
"""
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import flops_loop
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.ops import routes

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "ouro-2.6b-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
page, seqs = int(eng["page_size"]), int(eng["max_seqs"])
width = seqs + int(eng["prefill_chunk"])
page_bytes = flops_loop.kv_bytes_per_token(config) * page
pages = (int(sys.argv[2]) if len(sys.argv) > 2
         else int(eng["kv_pool_bytes"]) // page_bytes)
pages_per_seq = -(-int(eng["max_seq_len"]) // page)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
paged = model_cls(programs.serving_config(
    mcfg, max_seq_len=int(eng["max_seq_len"]), page_size=page,
    num_pages=pages, tensor_parallel=False, kv_cache_dtype="none"))
assert programs.paged_kv_page_bytes(plain, mcfg, page, "none") == page_bytes


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def on_chip(tree):
    return jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), tree)


i32 = jnp.int32
params = on_chip(jax.eval_shape(
    plain.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"])
meta = {"kv_lens": jnp.zeros((seqs,), i32),
        "page_indices": jnp.zeros((seqs, pages_per_seq), i32),
        "cu_q_lens": jnp.zeros((seqs + 1,), i32),
        "num_seqs": jnp.zeros((1,), i32),
        "new_kv_dest": jnp.zeros((width,), i32)}
cache = on_chip(jax.eval_shape(lambda: paged.init(
    jax.random.PRNGKey(0), jnp.zeros((1, width), i32),
    positions=jnp.zeros((1, width), i32), ragged_meta=meta))["cache"])
per_slot = [spec((seqs,), d) for d in (i32, i32, jnp.bool_, i32)]
table = spec((seqs, pages_per_seq), i32)
sampling = [spec((seqs,), d) for d in (
    i32, jnp.bool_, jnp.float32, i32, jnp.float32, jnp.uint32)]


def timed(lower):
    """(the lowered program, the host seconds tracing and lowering took:
    what every process pays again, whatever the compile cache holds)."""
    t0 = time.monotonic()
    low = lower()
    return low, time.monotonic() - t0


lowered = {
    "decode_block": timed(lambda: programs.build_decode_block(
        paged, sampled=False, max_seqs=seqs,
        block=int(eng["decode_block_size"]), page_size=page,
        max_seq_len=int(eng["max_seq_len"]),
        sample_base=jax.random.PRNGKey(0)).lower(
            params, cache, *per_slot, table, *sampling,
            spec((2,), jnp.uint32))),
    "fused_tick": timed(lambda: programs.build_fused_step(paged).lower(
        params, cache, spec((1, width), i32), spec((1, width), i32),
        spec((seqs,), i32), table, spec((seqs + 1,), i32), spec((1,), i32),
        spec((width,), i32), spec((seqs,), i32)))}

gib = 2.0 ** 30
for what, (low, lower_s) in lowered.items():
    t0 = time.monotonic()
    compiled = low.compile()
    seconds = time.monotonic() - t0
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print({"config": name, "program": what,
           "layers": mcfg.num_hidden_layers,
           "ut_steps": programs.kv_slots_per_layer(mcfg),
           "kv_pages": pages, "page_mib": page_bytes / 2 ** 20,
           "lower_s": round(lower_s, 1), "compile_s": round(seconds, 1),
           "argument_gib": mem.argument_size_in_bytes / gib,
           "temp_gib": mem.temp_size_in_bytes / gib,
           "total_gib": (mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes
                         + mem.temp_size_in_bytes) / gib,
           "paged_decode_attention_calls": len(re.findall(
               r"%paged_decode_attention[.\d]* = ", text)),
           "ragged_paged_attention_calls": len(re.findall(
               r"%ragged_paged_attention_kernel[.\d]* = ", text)),
           "while_loops": len(re.findall(r" while\(", text)),
           "routes": routes.taken()}, flush=True)
