"""What every cell shares: the manifest, look-up by name, the device check,
percentiles, the profiler slice and the last line.

Nothing here knows a configuration, a traffic mix or a metric by name.  A
cell is found in ``BENCHMARK.json``; its configuration in
``benchmark/configs/<config>.json``; its mix in
``benchmark/traffic/<traffic>.json``; its driver in
``benchmark/drivers/<kind>.py``; a generator in
``benchmark/generators/<name>.py``; a per-layer metric ``reader`` (several
cells listed) or ``reader.suffix`` (one cell) in
``benchmark/layer_metrics/<reader>.py``; the plain reference of a model
family in ``benchmark/reference/<family>.py``.  A later PR adds files and
manifest entries and edits none.
"""
from __future__ import annotations

import importlib
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "out")

# one clock for the benchmark and its load generator: CLOCK_MONOTONIC is
# the same in every process of the machine
now = time.monotonic


class BenchmarkError(RuntimeError):
    """The run cannot give a result: exit non-zero, print no result line."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration and its mix."""

    def __init__(self, name: str):
        man = manifest()
        rows = [w for w in man["workloads"] if w["name"] == name]
        if not rows:
            raise BenchmarkError(
                f"no workload {name!r} in BENCHMARK.json (have "
                f"{[w['name'] for w in man['workloads']]})")
        self.manifest = man
        self.name = name
        self.workload = rows[0]
        self.chips = int(self.workload["chips"])
        cfg_rows = [c for c in man["configs"]
                    if c["name"] == self.workload["config"]]
        if not cfg_rows:
            raise BenchmarkError(f"workload {name!r} names configuration "
                                 f"{self.workload['config']!r}, not listed")
        self.config = load_json(os.path.join(ROOT, cfg_rows[0]["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.workload["traffic"] + ".json"))

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def resolve(path: str) -> Any:
    """``package.module:attr`` -> the object."""
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def plugin(group: str, name: str) -> Any:
    """``benchmark/<group>/<name>.py``, found by name."""
    return importlib.import_module(f"benchmark.{group}.{name}")


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    vs = sorted(values)
    if not vs:
        raise BenchmarkError("percentile of no samples")
    k = (len(vs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


# -- the device --------------------------------------------------------------

def require_chips(chips: int, allow_cpu: bool = False) -> List[Any]:
    """The attached accelerators, exactly ``chips`` of them, or an error.
    ``allow_cpu`` is a function argument of the drivers' tests only; no
    command-line switch or environment variable sets it."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise BenchmarkError(f"this cell needs {chips} TPU chip(s); JAX "
                             f"found platform {platform!r}")
    if len(devices) < chips:
        raise BenchmarkError(f"this cell needs {chips} chip(s); JAX found "
                             f"{len(devices)}")
    return list(devices[:chips])


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise BenchmarkError(f"no published peaks for device kind "
                             f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def device_block(devices: List[Any]) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    return jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))


def init_params(model: Any, config: Dict[str, Any], seed: int) -> Any:
    """The configuration's weights from ``--seed``: the program's own
    ``model.init``, in the type they are served in, made on the device in
    ONE jitted call.  Where the configuration's ``weights`` asks for
    ``centre_matrices``, every leaf of two or more dimensions has its mean
    taken out in float32 and is rounded back: jax draws a bf16 normal 1.2 %
    of a standard deviation below zero, a rank-one all-ones part in every
    matrix, under which one token wins at every position on some seeds and
    a dozen keys are ill-conditioned on others (PERF.md section 6, PR 42)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    centre = bool(config.get("weights", {}).get("centre_matrices"))

    def centred(w):
        if w.ndim < 2:
            return w
        w32 = w.astype(jnp.float32)
        return (w32 - jnp.mean(w32)).astype(w.dtype)

    def make(key, tokens):
        params = model.init(key, tokens)["params"]
        return (jax.tree_util.tree_map(centred, params) if centre
                else params)

    return jax.jit(make)(seed_key(seed), np.zeros((1, 8), np.int32))


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``<repo>/.jax_cache``),
    for every program however quick to compile."""
    import jax
    from deepspeed_tpu.utils.compile_cache import \
        enable_compile_cache as enable

    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def sleep_until(t: float) -> None:
    time.sleep(max(t - now(), 0.0))


# -- the profiler slice ------------------------------------------------------

class TraceSlice:
    """The profiler on for ``[start, start + length)`` seconds of the
    window, in the process that holds the chip; the trace goes under
    ``benchmark/out/`` and is reduced by ``trace_reduce``."""

    def __init__(self, cell: str, seed: int):
        self.dir = os.path.join(OUT_DIR, f"trace-{cell}-{seed}")
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t_start = now()

    def stop(self) -> None:
        import jax

        self.t_stop = now()
        jax.profiler.stop_trace()

    def reduce(self) -> Any:
        from benchmark import trace_reduce

        return trace_reduce.reduce_dir(self.dir)


# -- the last line -----------------------------------------------------------

def reader_of(metric_name: str) -> str:
    """``reader`` or ``reader.suffix`` -> the reader's file name."""
    return metric_name.split(".", 1)[0]


def layer_metrics(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-layer metric this cell lists, each from its own reader;
    a reader that finds nothing to read returns None and is left out."""
    out: Dict[str, Any] = {}
    for m in cell.metrics("per_layer"):
        value = plugin("layer_metrics", reader_of(m["name"])).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(cell: Cell, values: Dict[str, float]
                       ) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for m in cell.metrics("end_to_end"):
        if m["name"] not in values:
            raise BenchmarkError(f"the driver gave no {m['name']!r}, which "
                                 f"cell {cell.name!r} must report")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def log(obj: Dict[str, Any]) -> None:
    """An earlier line: whatever is worth reading and is not the result."""
    print(json.dumps(obj), flush=True)


def write_out(name: str, obj: Any) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f)
    return path


def compared(value: float, limit: float) -> Dict[str, float]:
    """One number the run's ``correct`` was decided on, beside its limit
    (a number that is not finite is written as the largest float: the line
    stays JSON)."""
    value = float(value)
    return {"value": value if math.isfinite(value) else sys.float_info.max,
            "limit": float(limit)}


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None,
                check: Optional[Dict[str, Any]] = None) -> str:
    """The last line.  ``check`` holds every number compared for
    ``correct``, each with its limit; it comes last in the line and is
    printed as the last line of standard error too."""
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if check is not None:
        line["check"] = check
        print("check " + json.dumps(check), file=sys.stderr, flush=True)
    return json.dumps(line)


def build_model_config(config: Dict[str, Any]) -> Any:
    """The program's config dataclass from the configuration file: every
    top-level key of the file that is a field of the dataclass (the
    source's own names), then ``model_overrides``."""
    import dataclasses

    import jax.numpy as jnp

    cls = resolve(config["family"]["config"])
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in config.items() if k in fields}
    for k, v in config.get("model_overrides", {}).items():
        kw[k] = getattr(jnp, v) if k in ("dtype", "param_dtype") else v
    return cls(**kw)
