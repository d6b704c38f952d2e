"""The program's own host spans, read from the profiler's trace of the
traced run, and the device's idle time charged to them.

The serving loop's stage brackets are ``jax.profiler.TraceAnnotation``s
(``deepspeed_tpu/telemetry/tracer.py:Span``): with the profiler on they are
events of the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds the
device planes, their counts the events' stats, on the profiler's clock.
Everything here is in that file's own nanoseconds; no offset between
``perf_counter``, ``monotonic`` and the profiler is used anywhere.

A program without these spans (the parent of the PR that added them) gives
a trace without them: ``load`` then finds no worker thread, and every
reader built on it returns None.

Definitions.  The *idle intervals* of a chip are the complement of the busy
union of its ``XLA Ops`` line inside the traced extent, as ``trace_reduce``
defines busy.  The *worker* is the host thread whose line holds
``replica.op`` events.  At any instant the worker is in exactly one
*class*: ``between_ops`` outside every ``replica.op``; else the class of
the innermost enclosing span that has one (``CLASS_OF``); else ``unnamed``.
So the five shares partition the idle time and sum to the idle share.

``between_ops`` is the worker waiting for its next op, so what it waits for
is on the *pump* thread, the one whose line holds ``router.pump`` events
(the front door's loop: it takes requests in through ``router.submit`` and
hands the worker its ops).  ``between_ops_by_pump`` splits that share by
the innermost ``router.*`` span the pump thread was in; no metric reads it
yet, ``benchmark/idle_report.py`` prints it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness as H
from benchmark import trace_reduce as TR

HOST_PLANE = "/host:CPU"
PREFIXES = ("engine.", "replica.", "router.")
OP = "replica.op"
PUMP = "router.pump"
ELSEWHERE = "elsewhere"     # the pump thread in no ``router.*`` span
CLASS_OF = {
    "engine.plan": "plan",
    "engine.upload": "feed", "engine.rng_split": "feed",
    "engine.dispatch": "feed",
    "engine.sample": "sample", "engine.fetch": "sample",
    "engine.harvest": "sample", "engine.reap": "sample",
}
CLASSES = ("plan", "feed", "sample", "between_ops", "unnamed")

HostEvent = Tuple[str, float, float, Dict[str, Any]]   # name, start, dur, stats
Segment = Tuple[float, float, str]                     # start, end, class


@dataclasses.dataclass
class HostTrace:
    extent: Tuple[float, float]                 # device extent, ns
    idle: List[List[Tuple[float, float]]]       # per chip, ns intervals
    threads: Dict[int, List[HostEvent]]         # host line -> program spans

    @property
    def window_s(self) -> float:
        return (self.extent[1] - self.extent[0]) / 1e9

    def thread_of(self, name: str) -> Optional[List[HostEvent]]:
        """The one thread whose line holds spans called ``name``."""
        rows = [evs for evs in self.threads.values()
                if any(e[0] == name for e in evs)]
        return rows[0] if len(rows) == 1 else None

    def worker(self) -> Optional[List[HostEvent]]:
        """The one thread that runs replica ops.  Several workers (replicas
        on several chips) cannot be told apart by chip here: None."""
        return self.thread_of(OP)

    def spans(self, name: str, inside: Optional[str] = None
              ) -> List[HostEvent]:
        """The worker's spans called ``name``; with ``inside``, only those
        nested in a recorded span of that name.  The profiler records a
        span only if it began and ended in the session, so at the slice's
        edges leaves appear without the step and the op that enclose
        them; sums over parents and children must leave those out."""
        evs = [e for e in (self.worker() or []) if e[0] == name]
        if inside is None:
            return evs
        parents = [(e[1], e[1] + e[2]) for e in self.worker() or []
                   if e[0] == inside]
        return [e for e in evs if any(a <= e[1] and e[1] + e[2] <= b
                                      for a, b in parents)]


def idle_intervals(planes: TR.Planes
                   ) -> Tuple[Tuple[float, float],
                              List[List[Tuple[float, float]]]]:
    """The traced extent over all chips and each chip's idle intervals in
    it (``trace_reduce``'s busy: the ``XLA Ops`` line, the ``XLA Modules``
    line where there is none)."""
    busy = []
    for pname, lines in planes.items():
        ops = lines.get(TR.OPS_LINE) or lines.get(TR.MODULES_LINE, [])
        if not ops:
            raise TR.TraceError(f"plane {pname!r} holds no operation")
        busy.append(TR.union([(s, s + d) for _, s, d in ops]))
    lo = min(b[0][0] for b in busy)
    hi = max(b[-1][1] for b in busy)
    idle = []
    for merged in busy:
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle.append([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                     if b > a])
    return (lo, hi), idle


def from_planes(device: TR.Planes,
                host: Dict[int, List[HostEvent]]) -> HostTrace:
    if not device:
        raise TR.TraceError("the trace holds no TPU device plane")
    extent, idle = idle_intervals(device)
    return HostTrace(extent=extent, idle=idle, threads=host)


def read_planes(path: str
                ) -> Tuple[TR.Planes, Dict[int, List[HostEvent]]]:
    """One read of the file: the device planes in ``trace_reduce``'s
    neutral form, and of the host plane the program's spans with stats,
    by host line (a thread)."""
    from jax.profiler import ProfileData

    device: TR.Planes = {}
    host: Dict[int, List[HostEvent]] = {}
    for plane in ProfileData.from_file(path).planes:
        if TR.DEVICE_PLANE.match(plane.name):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events)
        elif plane.name == HOST_PLANE:
            for k, line in enumerate(plane.lines):
                evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns),
                        dict(ev.stats))
                       for ev in line.events if ev.name.startswith(PREFIXES)]
                if evs:
                    host[k] = evs
    return device, host


def load(ctx: Dict[str, Any]) -> Optional[HostTrace]:
    """The host trace of THIS run, or None.  ``ctx`` carries no path: the
    newest ``benchmark/out/trace-<cell>-*`` is taken, and refused unless
    its device extent is the one the driver reduced (``ctx["trace"]``), so
    a directory left by another run is never read as this one's.  Kept on
    ``ctx`` so that the readers of one run parse the file once."""
    if "_host_trace" in ctx:
        return ctx["_host_trace"]
    ctx["_host_trace"] = out = _load(ctx)
    return out


def _load(ctx: Dict[str, Any]) -> Optional[HostTrace]:
    red = ctx.get("trace")
    dirs = glob.glob(os.path.join(H.OUT_DIR, f"trace-{ctx['cell'].name}-*"))
    if red is None or not dirs:
        return None
    try:
        ht = from_planes(*read_planes(
            TR.newest_xplane(max(dirs, key=os.path.getmtime))))
    except TR.TraceError:
        return None
    if abs(ht.window_s - red.window_s) > 1e-6:
        return None
    return ht


# -- a thread's time line, flattened -----------------------------------------

def _worker_class(name: str, inherited: Optional[str]) -> Optional[str]:
    return "unnamed" if name == OP else CLASS_OF.get(name, inherited)


def router_span(name: str, inherited: Optional[str]) -> Optional[str]:
    return name if name.startswith("router.") else inherited


def segments(events: List[HostEvent], classify=_worker_class
             ) -> List[Segment]:
    """One thread's time inside its spans as disjoint ``(start, end,
    class)`` pieces.  Spans of one thread nest; ``classify(name,
    enclosing class)`` names a span's class, and a piece belongs to the
    innermost span.  For the worker (the default): the innermost span with
    a class of its own, ``unnamed`` where only ``replica.op`` (or spans
    without a class, such as ``engine.step``) enclose it."""
    evs = sorted(((s, s + d, n) for n, s, d, _ in events),
                 key=lambda e: (e[0], -e[1]))
    out: List[Segment] = []
    stack: List[Tuple[float, Optional[str]]] = []       # (end, class)
    cursor = 0.0

    def advance(upto: float) -> None:
        nonlocal cursor
        cls = stack[-1][1] if stack else None
        if cls is not None and upto > cursor:
            if out and out[-1][1] == cursor and out[-1][2] == cls:
                out[-1] = (out[-1][0], upto, cls)       # the same piece
            else:
                out.append((cursor, upto, cls))
        cursor = max(cursor, upto)

    for start, end, name in evs:
        while stack and stack[-1][0] <= start:
            advance(stack[-1][0])
            stack.pop()
        advance(start)
        stack.append((end, classify(name, stack[-1][1] if stack else None)))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


def charge(intervals: List[Tuple[float, float]], segs: List[Segment],
           default: str) -> List[Segment]:
    """Cut ``intervals`` by ``segs`` (both sorted and disjoint): the pieces
    of every interval, each with the class of the segment that covers it,
    ``default`` where none does.  A gap that straddles two spans is split
    between them."""
    out: List[Segment] = []
    k = 0
    for a, b in intervals:
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        j, cur = k, a
        while j < len(segs) and segs[j][0] < b:
            s, e, cls = segs[j]
            s, e = max(s, a), min(e, b)
            if s > cur:
                out.append((cur, s, default))
            out.append((s, e, cls))
            cur = e
            j += 1
        if b > cur:
            out.append((cur, b, default))
    return out


def _seconds(pieces_by_chip: List[List[Segment]], classes=()
             ) -> Dict[str, float]:
    """Seconds per class, mean over chips."""
    out = {c: 0.0 for c in classes}
    for pieces in pieces_by_chip:
        for a, b, cls in pieces:
            out[cls] = out.get(cls, 0.0) + b - a
    return {c: v / 1e9 / len(pieces_by_chip) for c, v in out.items()}


def idle_pieces(ht: HostTrace) -> Optional[List[List[Segment]]]:
    """Each chip's idle intervals cut by the worker's classes; None where
    the trace has no worker thread."""
    events = ht.worker()
    if events is None:
        return None
    segs = segments(events)
    return [charge(chip, segs, "between_ops") for chip in ht.idle]


def idle_by_class(ht: HostTrace) -> Optional[Dict[str, float]]:
    """Seconds of device idle time per class, mean over chips; None where
    the trace has no worker thread."""
    pieces = idle_pieces(ht)
    return None if pieces is None else _seconds(pieces, CLASSES)


def between_ops_by_pump(ht: HostTrace) -> Optional[Dict[str, float]]:
    """Seconds of the ``between_ops`` idle time by the innermost
    ``router.*`` span the pump thread was in (``elsewhere`` in none: the
    front door's own loop), mean over chips; None without a worker or a
    pump thread."""
    pieces, pump = idle_pieces(ht), ht.thread_of(PUMP)
    if pieces is None or pump is None:
        return None
    segs = segments(pump, router_span)
    return _seconds([
        charge([(a, b) for a, b, cls in chip if cls == "between_ops"],
               segs, ELSEWHERE) for chip in pieces])


def idle_pct(ctx: Dict[str, Any], cls: str) -> Optional[float]:
    """What the five ``idle_*_pct`` readers return: the class's idle
    seconds as a percentage of the traced extent."""
    ht = load(ctx)
    by = None if ht is None else idle_by_class(ht)
    return None if by is None else 100.0 * by[cls] / ht.window_s
