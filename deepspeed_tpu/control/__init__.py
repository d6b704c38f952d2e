"""Closed-loop control plane: the signal plane drives the knobs.

The system's perf-critical knobs (harvest interval, async depth,
KV-tiering prefetch and budgets, AIO buffer count, decode block, router
admission thresholds) were hand-tuned per host; PRs 10 and 13 built the
signal plane (tracer spans, histogram quantiles, SLO burn rates, per-stage
counters) that makes tuning them *observable*.  This package closes the loop:

- :mod:`~deepspeed_tpu.control.knobs` — the typed knob surface
  (:class:`KnobRegistry`): bounds, step, cooldown, apply callbacks
  wired into the ragged engine, tiered KV store, router, and moment
  stream; recompile-triggering knobs are fenced offline-only.
- :mod:`~deepspeed_tpu.control.controller` — the online
  :class:`Controller`: rule + hill-climb policy with hysteresis and an
  oscillation guard, every decision a ``cat="control"`` trace event
  plus ``dstpu_control_*`` metrics.
- :mod:`~deepspeed_tpu.control.profile` — the offline ``--autotune``
  sweep (on the ``autotuning/`` ExperimentScheduler substrate) and the
  per-host profile that seeds the online starting point.

``DSTPU_CONTROL=0`` is the kill switch: :func:`control_enabled` gates
every attach point, so the armed system degrades to the structurally
pre-control one.
"""
from __future__ import annotations

import os

from deepspeed_tpu.control.controller import (Controller, Rule,
                                              engine_signal_feed,
                                              prefetch_rule,
                                              slo_shed_rule)
from deepspeed_tpu.control.knobs import (Knob, KnobRegistry, router_knobs,
                                         swapper_knobs)
from deepspeed_tpu.control.profile import (HostProfile, autotune_serving,
                                           fingerprint_key,
                                           host_fingerprint, load_profile,
                                           save_profile)

__all__ = ["Controller", "Rule", "Knob", "KnobRegistry", "HostProfile",
           "autotune_serving", "control_enabled", "engine_signal_feed",
           "fingerprint_key", "host_fingerprint", "load_profile",
           "prefetch_rule", "router_knobs", "save_profile",
           "slo_shed_rule", "swapper_knobs"]


def control_enabled() -> bool:
    """The ``DSTPU_CONTROL=0`` kill switch (default: enabled — but the
    controller still only runs where config/kwargs arm it)."""
    return os.environ.get("DSTPU_CONTROL", "1") != "0"
