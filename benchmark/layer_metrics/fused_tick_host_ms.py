"""What the host spends on ONE fused tick, from inside: over the
``engine.step`` spans of the traced slice that hold an ``engine.dispatch``
span with ``program=fused``, (sum of the steps' durations - the
``engine.fetch`` and ``engine.wait`` spans inside them, the time the host
was only blocked on the device) / the fused ``engine.dispatch`` spans inside
them, in milliseconds.  The profiler records a span only if it began and
ended in the session, so a step the slice's edge cut is not there and what
is left of its leaves is not counted.

``engine_step_host_ms`` divides ALL steps by ALL dispatches, so it moves
with the slice's mix of fused ticks and decode blocks at the same code;
this one reads the steps of one program.  None where the trace holds no
worker or no such step (a program without the spans, a cell without fused
ticks in its slice).

The first call logs ``fused_tick_leaves`` on an earlier line: of every
``engine.*`` span inside those steps the milliseconds and the count a tick,
the milliseconds in it and in no span nested in it (``self_ms``) and the
mean of each of its numeric stats; ``uncovered_ms``, the steps' time in no
span at all; ``plan_covered_pct``, the share of ``engine.plan``'s time that
its leaves ``engine.plan.*`` hold; ``host_ms_p50`` and ``host_ms_max``, the
median and the longest step's host time beside the metric's mean.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional

from benchmark import harness as H
from benchmark.layer_metrics import host_spans

STEP, DISPATCH, PLAN = "engine.step", "engine.dispatch", "engine.plan"
WAITS = ("engine.fetch", "engine.wait")


def fused_steps(ht: host_spans.HostTrace
                ) -> List[List[host_spans.HostEvent]]:
    """The worker's recorded steps that dispatched a fused tick, each as
    its events: the ``engine.step`` span first, then every span inside
    it."""
    worker = sorted(ht.worker() or [], key=lambda e: (e[1], -e[2]))
    starts = [e[1] for e in worker]
    out = []
    for k, ev in enumerate(worker):
        if ev[0] != STEP:
            continue
        end = ev[1] + ev[2]
        inside = [e for e in worker[k + 1:bisect.bisect_right(starts, end)]
                  if e[1] + e[2] <= end]
        if any(e[0] == DISPATCH and e[3].get("program") == "fused"
               for e in inside):
            out.append([ev] + inside)
    return out


def _innermost(name: str, inherited: Optional[str]) -> Optional[str]:
    return name


def leaves(steps: List[List[host_spans.HostEvent]], ticks: int
           ) -> Dict[str, Any]:
    """The ``fused_tick_leaves`` line of ``steps``, a tick of ``ticks``."""
    per = lambda ns: round(ns / 1e6 / ticks, 4)  # noqa: E731
    total: Dict[str, float] = {}
    own: Dict[str, float] = {}
    count: Dict[str, int] = {}
    stats: Dict[str, Dict[str, float]] = {}
    for events in steps:
        for name, _, dur, st in events:
            total[name] = total.get(name, 0.0) + dur
            count[name] = count.get(name, 0) + 1
            for key, v in st.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    by = stats.setdefault(name, {})
                    by[key] = by.get(key, 0.0) + v
        for a, b, name in host_spans.segments(events, _innermost):
            own[name] = own.get(name, 0.0) + b - a
    by_span: Dict[str, Any] = {}
    for name in sorted(total):
        if name == STEP:
            continue
        row = by_span[name] = {"ms": per(total[name]),
                               "self_ms": per(own.get(name, 0.0)),
                               "n": round(count[name] / ticks, 3)}
        if name.startswith(PLAN + ".") and name in stats:
            # what explains a plan leaf's cost, a span of it
            row["stats"] = {k: round(v / count[name], 3)
                            for k, v in stats[name].items()}
    out = {"by_span": by_span, "uncovered_ms": per(own.get(STEP, 0.0))}
    if total.get(PLAN):
        out["plan_covered_pct"] = round(
            100.0 * (1.0 - own.get(PLAN, 0.0) / total[PLAN]), 2)
    return out


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    steps = fused_steps(ht)
    ticks = sum(e[0] == DISPATCH and e[3].get("program") == "fused"
                for events in steps for e in events)
    if not ticks:
        return None
    wall_ns = sum(events[0][2] for events in steps)
    wait_ns = sum(e[2] for events in steps for e in events if e[0] in WAITS)
    if "_fused_tick_logged" not in ctx:
        ctx["_fused_tick_logged"] = True
        # the metric is a MEAN: a step that ran while another thread held
        # the interpreter lock (a request being parsed) counts in full; the
        # median step says what a tick costs when nothing contends
        own = sorted(events[0][2] - sum(e[2] for e in events if e[0] in WAITS)
                     for events in steps)
        H.log({"fused_tick_leaves": leaves(steps, ticks), "ticks": ticks,
               "steps": len(steps),
               "wait_ms": round(wait_ns / 1e6 / ticks, 4),
               "host_ms_p50": round(own[len(own) // 2] / 1e6, 4),
               "host_ms_max": round(own[-1] / 1e6, 4)})
    return (wall_ns - wait_ns) / 1e6 / ticks
