"""Autotuner: discover the best ZeRO stage + micro-batch configuration.

TPU-native re-design of the reference autotuner
(``autotuning/autotuner.py:42 Autotuner``: memory-model stage pruning
``:278``, micro-batch search ``:851``, experiment records ``:708``,
``write_optimal_config:1075``).  The reference launches subprocess
experiment sweeps and scrapes metrics; on TPU two things collapse the
cost:

- **model info is free**: parameter counts come from ``jax.eval_shape``
  (no profile run), and
- **memory probes are compile-only**: ``jit(...).lower().compile()``
  reports XLA's exact per-device buffer usage without executing a step —
  an OOM shows up as a compile-time estimate, not a crashed run.

The tuning loop mirrors the reference strategy: rank ZeRO stages by the
Adam memory model (``:278`` formulas), prune stages whose instantiation
memory cannot fit, then for each surviving stage search micro-batch
sizes (doubling sweep, like the reference's min/max probe + list sweep),
measure each candidate with the injected ``runner`` (by default: build a
real engine and time ``train_batch``), and keep records.  ``tune()``
returns the best config; ``write_optimal_config`` saves it.
"""
from __future__ import annotations

import copy
import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

ADAM_BYTES_PER_PARAM_FP32 = 8        # two fp32 moments
MASTER_BYTES_PER_PARAM = 4


@dataclass
class ModelInfo:
    num_params: int
    hidden_size: int = 0
    num_layers: int = 0

    @staticmethod
    def from_model(model, example_batch, rng=None) -> "ModelInfo":
        import jax
        import numpy as np

        def init():
            # the default key is made INSIDE the trace: nothing here may
            # touch a backend, or a tuner whose experiments run in child
            # processes (make_subprocess_runner) would hold their chip
            key = rng if rng is not None else jax.random.PRNGKey(0)
            return model.init({"params": key, "dropout": key},
                              example_batch)

        shapes = jax.eval_shape(init)
        n = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes))
        return ModelInfo(num_params=n)


class Autotuner:
    """``Autotuner(model_info, base_config, runner).tune()`` -> best
    (ds_config, metric).  ``runner(ds_config) -> samples_per_sec`` (or
    any higher-is-better metric); raise/return None for OOM/failure."""

    def __init__(self, model_info: ModelInfo, base_config: Dict[str, Any],
                 runner: Optional[Callable[[Dict], Optional[float]]] = None,
                 num_chips: Optional[int] = None,
                 hbm_bytes: Optional[float] = None,
                 metric: str = "throughput"):
        self.model_info = model_info
        self.base_config = copy.deepcopy(base_config)
        at = dict(self.base_config.pop("autotuning", {}))
        self.tuner_config = at
        self.metric_name = at.get("metric", metric)
        self.fast = bool(at.get("fast", True))
        self.max_mbs_cap = int(at.get("max_train_micro_batch_size_per_gpu",
                                      1024))
        self.start_mbs = int(at.get("min_train_micro_batch_size_per_gpu",
                                    1))
        self.stages = at.get("zero_stages", [0, 1, 2, 3])
        self.runner = runner or self._default_runner
        # asked of the backend only when not given: with a subprocess
        # runner pass both, so that this (parent) process stays off the
        # chip its experiments need
        if num_chips is None:
            import jax

            num_chips = len(jax.devices())
        self.num_chips = num_chips
        self.hbm_bytes = hbm_bytes or self._detect_hbm()
        self.records: List[Dict[str, Any]] = []
        self.best: Optional[Tuple[Dict, float]] = None

    # -- hardware/memory model -----------------------------------------

    def _detect_hbm(self) -> float:
        import jax

        d = jax.devices()[0]
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            pass
        return float(stats.get("bytes_limit", 16e9))

    def instantiation_memory(self, zero_stage: int,
                             fp16: Optional[bool] = None) -> float:
        """Reference ``get_instantiation_memory_required_per_gpu:278``:
        Adam memory model per chip (params + grads + optimizer states,
        divided by the shards each stage introduces)."""
        n = self.model_info.num_params
        low_prec = fp16 if fp16 is not None else self._low_precision()
        params = n * (2 if low_prec else 4)
        grads = n * (2 if low_prec else 4)
        # master copy + both moments when training in low precision
        optimizer = n * ((MASTER_BYTES_PER_PARAM +
                          ADAM_BYTES_PER_PARAM_FP32) if low_prec
                         else ADAM_BYTES_PER_PARAM_FP32)
        shards = max(self.num_chips, 1)
        if zero_stage >= 1:
            optimizer /= shards
        if zero_stage >= 2:
            grads /= shards
        if zero_stage >= 3:
            params /= shards
        return params + grads + optimizer

    def _low_precision(self) -> bool:
        return bool(self.base_config.get("fp16", {}).get("enabled") or
                    self.base_config.get("bf16", {}).get("enabled"))

    def memory_fits(self, zero_stage: int, margin: float = 0.85) -> bool:
        return self.instantiation_memory(zero_stage) < \
            self.hbm_bytes * margin

    # -- experiment generation + search --------------------------------

    def _candidate_stages(self) -> List[int]:
        user = self.base_config.get("zero_optimization", {}).get("stage")
        stages = [user] if user is not None else list(self.stages)
        fits = [s for s in stages if self.memory_fits(s)]
        dropped = sorted(set(stages) - set(fits))
        if dropped:
            logger.info(f"autotuning: pruned zero stages {dropped} "
                        "(instantiation memory exceeds HBM)")
        # prefer lighter-comm stages first (reference tuning order)
        return sorted(fits)

    def _config_for(self, stage: int, mbs: int) -> Dict[str, Any]:
        cfg = copy.deepcopy(self.base_config)
        cfg.setdefault("zero_optimization", {})["stage"] = stage
        cfg["train_micro_batch_size_per_gpu"] = mbs
        cfg.pop("train_batch_size", None)
        cfg.setdefault("gradient_accumulation_steps", 1)
        return cfg

    def _measure(self, stage: int, mbs: int) -> Optional[float]:
        cfg = self._config_for(stage, mbs)
        t0 = time.perf_counter()
        try:
            val = self.runner(cfg)
        except Exception as e:
            logger.info(f"autotuning: stage={stage} mbs={mbs} failed: {e}")
            val = None
        rec = {"zero_stage": stage, "micro_batch_size": mbs,
               self.metric_name: val,
               "tuning_seconds": time.perf_counter() - t0}
        from deepspeed_tpu.autotuning.scheduler import \
            record_experiment_metrics
        record_experiment_metrics(val, rec["tuning_seconds"])
        self.records.append(rec)
        if val is not None and (self.best is None or val > self.best[1]):
            self.best = (cfg, val)
        return val

    def tune(self) -> Tuple[Optional[Dict[str, Any]], Optional[float]]:
        """Doubling micro-batch sweep per surviving stage; a stage stops
        when a size fails or the metric plateaus (reference
        ``tune_space`` early-stop semantics)."""
        for stage in self._candidate_stages():
            mbs = self.start_mbs
            prev = None
            while mbs <= self.max_mbs_cap:
                val = self._measure(stage, mbs)
                if val is None:
                    break
                if prev is not None and val < prev * 1.02:
                    break                      # throughput plateau
                prev = val
                mbs *= 2
            if self.fast and self.best is not None:
                # fast mode: first fitting stage's sweep is enough unless
                # a later stage is needed to fit at all
                break
        if self.best is None:
            logger.warning("autotuning: no configuration succeeded")
            return None, None
        return self.best

    # -- reporting (reference print_tuning_results / write_optimal) -----

    def print_tuning_results(self) -> None:
        logger.info("autotuning records:")
        for r in self.records:
            logger.info(f"  stage={r['zero_stage']} "
                        f"mbs={r['micro_batch_size']} "
                        f"{self.metric_name}={r[self.metric_name]}")
        if self.best is not None:
            logger.info(f"best: {json.dumps(self.best[0])} -> "
                        f"{self.best[1]:.2f} {self.metric_name}")

    def write_optimal_config(self, path: str) -> None:
        assert self.best is not None, "tune() first"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.best[0], f, indent=2)

    # -- default runner: real engine, timed steps -----------------------

    def _default_runner(self, ds_config: Dict[str, Any]
                        ) -> Optional[float]:
        raise NotImplementedError(
            "pass runner= (ds_config -> samples/sec); the engine-backed "
            "default needs model/example_batch context — use "
            "engine_runner(model, example_batch_fn)")


def engine_runner(model, batch_fn: Callable[[int], Any], steps: int = 3,
                  topology=None):
    """Build the default measurement runner: instantiate a real engine for
    each candidate config and time ``train_batch`` (samples/sec).
    ``batch_fn(global_batch_size)`` supplies a batch of that size."""
    import jax
    import numpy as np

    def run(ds_config: Dict[str, Any]) -> float:
        import deepspeed_tpu

        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=ds_config, topology=topology,
            example_batch=batch_fn(1), rng=jax.random.PRNGKey(0))
        batch = batch_fn(engine.config.train_batch_size)
        engine.train_batch(batch=batch)        # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch=batch)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        return engine.config.train_batch_size / dt

    return run
