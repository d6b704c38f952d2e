#!/usr/bin/env python3
"""Compile the two serving programs of an EXPERT configuration for a
DESCRIBED v5e chip, without a chip: the fused SplitFuse tick
(``max_seqs + prefill_chunk`` rows) and one decode tick (``max_seqs``
rows), each over the whole paged KV pool with the engine's ``live`` rows,
and print what the TPU compiler needs on the device, whether the Pallas
paged kernel and the grouped expert matmul's kernels are in them, and the
largest temporary the expert layer leaves: a dense ``experts x rows x
hidden`` operand would be refused here.

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_moe_v5e.py [config]

Beside ``serve_v5e.py`` (which takes a dense configuration and is not
edited): the same construction, plus the ``live`` mask of ``ragged_meta``,
the ``moe_stats`` collection the engine makes mutable, and the checks of
the expert layer.  A script, never imported by a test: it describes the
topology at top level.  A compile that passes is not a chip run.
"""
import dataclasses
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

from benchmark import flops
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.ops import routes

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "olmoe-1b-7b-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
page, seqs = int(eng["page_size"]), int(eng["max_seqs"])
pages = int(eng["kv_pool_bytes"]) // (flops.kv_bytes_per_token(config) * page)
pages_per_seq = -(-int(eng["max_seq_len"]) // page)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
paged = model_cls(dataclasses.replace(
    mcfg, decode=True, ragged_decode=False, paged_decode=True,
    max_cache_len=int(eng["max_seq_len"]), scan_layers=False,
    kv_page_size=page, kv_num_pages=pages, kv_cache_dtype="none"))
experts, hidden = int(config["num_experts"]), int(config["hidden_size"])
top_k = int(config["num_experts_per_tok"])


def on_chip(tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)


def meta_for(t):
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return {"kv_lens": i32(seqs), "page_indices": i32(seqs, pages_per_seq),
            "cu_q_lens": i32(seqs + 1), "num_seqs": i32(1),
            "new_kv_dest": i32(t),
            "live": jax.ShapeDtypeStruct((t,), jnp.bool_)}


params = on_chip(jax.eval_shape(
    plain.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"])


def tick(params, cache, ids, pos, meta, rows):
    out, new = paged.apply({"params": params, "cache": cache}, ids,
                           positions=pos, mutable=["cache", "moe_stats"],
                           ragged_meta=meta)
    counts = jnp.stack(jax.tree_util.tree_leaves(new["moe_stats"]))
    return jnp.take(out[0][0], rows, axis=0), new["cache"], counts


gib = 2.0 ** 30
shape = re.compile(r"\b[a-z]+\d+\w*\[([\d,]+)\]")
for what, t in (("fused_tick", seqs + int(eng["prefill_chunk"])),
                ("decode_tick", seqs)):
    ids = jax.ShapeDtypeStruct((1, t), jnp.int32)
    meta = meta_for(t)
    cache = on_chip(jax.eval_shape(
        lambda: paged.init(jax.random.PRNGKey(0), jnp.zeros((1, t), jnp.int32),
                           positions=jnp.zeros((1, t), jnp.int32),
                           ragged_meta=jax.tree_util.tree_map(
                               lambda s: jnp.zeros(s.shape, s.dtype), meta))
    )["cache"])
    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        params, cache, on_chip(ids), on_chip(ids), on_chip(meta),
        on_chip(jax.ShapeDtypeStruct((seqs,), jnp.int32))).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    # what a capacity-free dense dispatch would build: experts x rows x
    # hidden in some order (dims of 1 aside)
    dims = [sorted(int(d) for d in m.split(",") if d != "1")
            for m in shape.findall(text)]
    dense = dims.count(sorted((experts, t, hidden)))
    print({"config": name, "program": what, "rows": t,
           "layers": mcfg.num_hidden_layers, "kv_pages": pages,
           "argument_gib": mem.argument_size_in_bytes / gib,
           "temp_gib": mem.temp_size_in_bytes / gib,
           "total_gib": (mem.argument_size_in_bytes
                         + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes
                         + mem.temp_size_in_bytes) / gib,
           "pallas_kernels": text.count(
               'custom_call_target="tpu_custom_call"'),
           "ragged_dot_calls": len(re.findall(r"= \S+ custom-call\(.*"
                                              r"ragged-dot", text)),
           "routed_copies": t * top_k,
           "operands_of_experts_x_rows_x_hidden": dense})
    assert dense == 0, "a dense [E, G, M] operand is back"
