"""Where a traced serving run's idle chip time went, from its profile alone.

    python benchmark/idle_report.py [benchmark/out/trace-<cell>-<seed> ...]

With no argument every ``benchmark/out/trace-*`` is read.  One JSON object
a trace on stdout, the source of PERF.md §5's host-side numbers.  The five
``idle_*_pct`` metrics say which class of the worker's spans the idle time
fell into; this says what a metric has no room for, and is how each span
and count of the serving loop that no metric reads is used:

``idle_pct``             the five shares, as the metrics report them
``between_ops_by_pump``  the ``between_ops`` share by the innermost
                         ``router.*`` span the pump thread was in
                         (``router.submit`` / ``router.prefix_hash``:
                         request intake; ``router.fold``: a finished step
                         folded; ``router.dispatch``: a request handed to a
                         replica; ``router.pump``: the rest of a round;
                         ``elsewhere``: the front door's own loop)
``between_ops_before``   the same share by the ``kind`` of the
                         ``replica.op`` that ended the wait (``put``: a
                         request arrived; ``step``: a hand-over between
                         two steps)
``largest_gaps``         the longest waits between two ops: ms, idle ms in
                         it, the next op's kind, ms of each pump span in it
``spans``                per span name: how many, their total ms, the
                         longest ms (``engine.upload`` against
                         ``engine.dispatch`` is the cost of feeding a tick)
``brackets_per_dispatch``  spans on both threads / ``engine.dispatch``
                         spans: what tracing costs is this x a bracket
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as H  # noqa: E402
from benchmark import trace_reduce as TR  # noqa: E402
from benchmark.layer_metrics import host_spans as HS  # noqa: E402

GAPS = 8


def _ms(ns: float) -> float:
    return round(ns / 1e6, 3)


def _overlap(intervals, a: float, b: float) -> float:
    return sum(min(b, y) - max(a, x) for x, y in intervals
               if y > a and x < b)


def report(ht: HS.HostTrace) -> Dict[str, Any]:
    """The report of one trace; ``{}`` where it holds no worker thread."""
    by = HS.idle_by_class(ht)
    if by is None:
        return {}
    pct = lambda s: round(100.0 * s / ht.window_s, 3)  # noqa: E731
    out: Dict[str, Any] = {
        "window_s": round(ht.window_s, 6),
        "idle_pct": {c: pct(v) for c, v in by.items()}}
    by_pump = HS.between_ops_by_pump(ht)
    if by_pump is not None:
        out["between_ops_by_pump"] = {n: pct(v) for n, v in by_pump.items()}
    worker = ht.worker()
    pump = ht.thread_of(HS.PUMP) or []
    psegs = HS.segments(pump, HS.router_span)
    ops = sorted((e for e in worker if e[0] == HS.OP), key=lambda e: e[1])
    idle = ht.idle[0]
    before: Dict[str, float] = {}
    gaps: List[Dict[str, Any]] = []
    for o1, o2 in zip(ops, ops[1:]):
        a, b = o1[1] + o1[2], o2[1]
        kind = str(o2[3].get("kind"))
        idle_ns = _overlap(idle, a, b)
        before[kind] = before.get(kind, 0.0) + idle_ns / 1e9
        inpump: Dict[str, float] = {}
        for s, e, name in HS.charge([(a, b)], psegs, HS.ELSEWHERE):
            inpump[name] = inpump.get(name, 0.0) + e - s
        gaps.append({"ms": _ms(b - a), "idle_ms": _ms(idle_ns),
                     "before": kind,
                     "pump_ms": {n: _ms(v) for n, v in inpump.items()}})
    out["between_ops_before"] = {k: pct(v) for k, v in before.items()}
    out["largest_gaps"] = sorted(gaps, key=lambda g: -g["ms"])[:GAPS]
    spans: Dict[str, List[float]] = {}
    for ev in list(worker) + list(pump):
        spans.setdefault(ev[0], []).append(ev[2])
    out["spans"] = {n: {"n": len(d), "ms": _ms(sum(d)), "max_ms": _ms(max(d))}
                    for n, d in sorted(spans.items())}
    dispatches = len(spans.get("engine.dispatch", []))
    if dispatches:
        out["brackets_per_dispatch"] = round(
            sum(len(d) for d in spans.values()) / dispatches, 2)
    return out


def main(argv: List[str]) -> int:
    dirs = argv or sorted(glob.glob(os.path.join(H.OUT_DIR, "trace-*")))
    if not dirs:
        print(f"no trace under {H.OUT_DIR}", file=sys.stderr)
        return 1
    for d in dirs:
        ht = HS.from_planes(*HS.read_planes(TR.newest_xplane(d)))
        print(json.dumps({"trace": os.path.basename(d.rstrip("/")),
                          **report(ht)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
