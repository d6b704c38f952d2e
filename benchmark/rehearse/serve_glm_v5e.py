#!/usr/bin/env python3
"""Compile the two ENGINE programs of a LATENT-attention configuration (a
pool of one ``[c | k_r]`` row a token a layer; a sigmoid router with a
selection bias beside a shared expert) for a DESCRIBED v5e chip, without a
chip: ``programs.build_fused_sample_step`` (``max_seqs + prefill_chunk``
rows) and ``programs.build_decode_block`` (``decode_block_size`` ticks of
``max_seqs`` rows), over the whole pool, as the engine builds them.  Prints
what the TPU compiler needs on the device, the kernels in each program and
the routes taken, and refuses a program whose latent attention fell to its
XLA twin or whose pool holds anything per head.

    JAX_PLATFORMS=cpu python benchmark/rehearse/serve_glm_v5e.py [config]

Beside ``serve_lfm2_v5e.py`` (not edited).  A script, never imported by a
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

from benchmark import flops_hybrid, flops_mla
from benchmark import harness as H

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

from deepspeed_tpu.inference import paged as paged_lib
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.models.llama import count_params
from deepspeed_tpu.ops import latent_attention, routes

routes.on_tpu = lambda: True        # the kernels' TPU branch, as on the chip

name = sys.argv[1] if len(sys.argv) > 1 else "glm-4.7-flash-serve"
config = H.load_json(os.path.join(H.HERE, "configs", name + ".json"))
eng = config["engine"]
mcfg = H.build_model_config(config)
model_cls = H.resolve(config["family"]["module"])
plain = model_cls(mcfg)
page, S = int(eng["page_size"]), int(eng["max_seqs"])
T = S + int(eng["prefill_chunk"])
page_bytes = programs.paged_kv_page_bytes(plain, mcfg, page, "none")
assert page_bytes == page * flops_mla.latent_bytes_per_token(
    config, stored=True), (
    "flops_mla.py and the pool disagree on a token's stored bytes")
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
W = paged_lib.latent_width(mcfg)
assert not states and all(l.shape == (pages, page, W) for l in pools), (
    "a pool leaf that is no [pages, page, W] latent pool")
nbytes = lambda ls: sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in ls)
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
layers = int(mcfg.num_hidden_layers)
print({"config": name, "parameters": count_params(params),
       "pages": pages, "page_bytes": page_bytes, "row_stored": W,
       "bytes_per_token": page_bytes // page, "pool_gib": nbytes(pools) / gib,
       "pool_tokens": (pages - 1) * page})
for what, lowered in (("fused_tick", fused), ("decode_block", block)):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    calls = lambda pat: len(re.findall(
        r"= \S+ custom-call\([^\n]*" + pat, text))
    report = {
        "program": what,
        "argument_gib": mem.argument_size_in_bytes / gib,
        "temp_gib": mem.temp_size_in_bytes / gib,
        "total_gib": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes
                      + mem.temp_size_in_bytes) / gib,
        "pallas_kernels": text.count('custom_call_target="tpu_custom_call"'),
        "latent_chunk_attention_calls": len(re.findall(
            rf"%{latent_attention.NAME_PREFILL}[.\d]* = ", text)),
        "latent_decode_attention_calls": len(re.findall(
            rf"%{latent_attention.NAME_DECODE}[.\d]* = ", text)),
        "ragged_dot_calls": calls("ragged-dot")}
    print(report)
    assert report["latent_decode_attention_calls"] == layers, (
        f"{what}: a layer's one-query rows are no Pallas call")
    assert report["latent_chunk_attention_calls"] == (
        layers if what == "fused_tick" else 0), (
        f"{what}: a layer's prompt chunks are no Pallas call")
    assert report["ragged_dot_calls"] >= 3 * flops_hybrid.expert_layers(
        config), f"{what}: an expert layer's grouped matmul is no kernel"
print({"routes": routes.taken()})
want = {k: v for k, v in config["expected_routes"].items()
        if k.startswith(latent_attention.KERNEL)}
assert routes.taken(prefix=latent_attention.KERNEL) == want, routes.taken()
