"""Device-time step measurement via jax.profiler (wall clock includes the
host's dispatch latency; XPlane device events don't).

Usage: python scripts/perf_trace.py [variant ...]   (perf_probe syntax)
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def device_step_ms(fn, n=3, tag="step") -> dict:
    """Run fn() n times under the profiler; return {event_prefix: ms/call}
    summing TPU-plane event durations."""
    d = f"/tmp/dstpu_trace_{tag}_{os.getpid()}"
    shutil.rmtree(d, ignore_errors=True)
    jax.profiler.start_trace(d)
    out = None
    for _ in range(n):
        out = fn()
    jax.device_get(jax.tree_util.tree_map(
        lambda x: jnp.sum(x).astype(jnp.float32) if hasattr(x, "shape") else x,
        out))
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    p = sorted(glob.glob(d + "/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(p)
    tot = {}
    for plane in pd.planes:
        if "TPU" not in plane.name:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("jit_"):
                    key = ev.name.split("(")[0]
                    tot[key] = tot.get(key, 0) + ev.duration_ns
    return {k: v / 1e6 / n for k, v in sorted(tot.items(),
                                              key=lambda kv: -kv[1])}


def run_variant(spec: str) -> None:
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.gpt2 import (GPT2LMLoss, flops_per_token,
                                           get_config)
    from bench import peak_flops

    kv = dict(item.split("=") for item in spec.split(",") if item)
    flash = bool(int(kv.get("flash", 1)))
    remat = kv.get("remat", "none")
    micro = int(kv.get("micro", 8))
    seq = int(kv.get("seq", 1024))
    preset = kv.get("preset", "gpt2-125m")
    zero = int(kv.get("zero", 0))
    opt = kv.get("opt", "AdamW")
    scan = bool(int(kv.get("scan", 1)))

    cfg_model = get_config(preset, n_positions=seq, dtype=jnp.bfloat16,
                           remat=remat != "none", remat_policy=remat,
                           scan_layers=scan, use_flash_attention=flash)
    topo = dist.initialize_mesh()
    dp = topo.zero_partition_count()
    ds_config = {
        "train_batch_size": micro * dp,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero},
        "optimizer": {"type": opt, "params": {"lr": 1e-4,
                                              "weight_decay": 0.01}},
        "steps_per_print": 1000000,
    }
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg_model.vocab_size, size=(micro * dp, seq), dtype=np.int32)}
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2LMLoss(cfg_model), config=ds_config, topology=topo,
        example_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    dbatch = engine.put_batch(batch)
    loss = engine.train_batch(batch=dbatch)  # compile
    float(jax.device_get(loss))

    times = device_step_ms(lambda: engine.train_batch(batch=dbatch),
                           tag=spec.replace(",", "_").replace("=", ""))
    step_ms = sum(times.values())
    tok = micro * dp * seq
    dev = jax.devices()[0]
    mfu = 100.0 * tok * flops_per_token(cfg_model, seq) / (
        step_ms / 1e3) / peak_flops(dev.device_kind) / len(jax.devices())
    print(f"TRACE {spec!r}: device step {step_ms:.1f} ms -> mfu={mfu:.1f}%  "
          f"breakdown={ {k: round(v, 1) for k, v in times.items()} }",
          flush=True)


if __name__ == "__main__":
    for v in (sys.argv[1:] or ["flash=1,remat=none,micro=8,opt=AdamW"]):
        run_variant(v)
