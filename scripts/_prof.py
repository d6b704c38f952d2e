"""Shared device-time measurement via jax.profiler XPlane events.

Wall clock includes the host's dispatch latency; TPU-plane event
durations don't.  One helper, imported by perf_trace.py /
moe_profile.py / llama_profile.py.
"""
from __future__ import annotations

import glob
import os
import shutil

import jax
import jax.numpy as jnp


def profile_device(fn, n: int = 3, tag: str = "step"):
    """Run ``fn()`` n times under the profiler.

    Returns ``(step_ms, ops)`` where ``step_ms`` is the per-call sum of
    ``jit_*`` TPU-plane event durations and ``ops`` is a list of
    ``(event_name, ms_per_call)`` sorted by cost (non-jit events — XLA op
    level — useful for breakdowns; nested events double-count, so treat
    the list as relative weights, not a partition of step_ms).
    """
    d = f"/tmp/dstpu_prof_{tag}_{os.getpid()}"
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
    ops = {}
    step_ms = 0.0
    for plane in pd.planes:
        if "TPU" not in plane.name:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("jit_"):
                    step_ms += ev.duration_ns / 1e6 / n
                    continue
                ops[ev.name] = ops.get(ev.name, 0) + ev.duration_ns / 1e6 / n
    return step_ms, sorted(ops.items(), key=lambda kv: -kv[1])
