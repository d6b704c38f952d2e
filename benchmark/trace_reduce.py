"""From a profiler trace to numbers: device-plane durations per
``jit_<name>``, the busy union, self times, gaps and collectives.  A fault
in the trace raises; nothing is swallowed.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, events with a
start and a duration in nanoseconds.  A TPU chip is the plane
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per run of a
jitted program (``jit_ragged_decode_block(...)``), its line ``XLA Ops`` one
event per HLO operation, a ``while`` or a ``fusion`` enclosing its body's
operations on the same line.  (Seen by hand on the chip, my chip run,
PR 24.)

Everything below works on a neutral form -- ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}`` -- so that the arithmetic is tested on a hand-built trace
and the loader on a hand-encoded ``xplane.pb`` (``benchmark/tests``).

Definitions.  *Busy* on a chip is the union of its operations' intervals
(the ``XLA Ops`` line; the ``XLA Modules`` line where there is none); the
*idle share* is 1 - busy / traced extent, where the extent runs from the
first device event's start to the last one's end over all chips; numbers
over several chips are means over chips.  An operation's *self time* is its
duration minus what its enclosed operations cover.  A collective's
*exposed* time is the self time of events named as collectives: the
operations line is one chip's sequential time line, so while a collective
operation (or the ``-done`` half of an asynchronous one) holds it, nothing
else runs there.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from typing import Any, Dict, List, Tuple

Event = Tuple[str, float, float]            # name, start_ns, dur_ns
Planes = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast", re.I)


class TraceError(RuntimeError):
    """The trace cannot be reduced; never answered with an empty result."""


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise TraceError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, plane_filter=DEVICE_PLANE) -> Planes:
    """The planes whose name matches ``plane_filter``, in neutral form."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Planes = {}
    for plane in data.planes:
        if plane_filter is not None and not plane_filter.match(plane.name):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        out[plane.name] = lines
    return out


def describe(path: str, per_line: int = 4) -> List[str]:
    """What a trace holds, for reading one by hand: every plane and line
    with its event count and first few names."""
    rows = []
    for pname, lines in load_xplane(path, plane_filter=None).items():
        rows.append(f"plane {pname!r}")
        for lname, evs in lines.items():
            names = ", ".join(sorted({e[0] for e in evs})[:per_line])
            rows.append(f"  line {lname!r}: {len(evs)} events [{names}]")
    return rows


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(events: List[Event]) -> List[Tuple[str, float]]:
    """``(name, self_ns)`` per event of one line: duration minus the part
    covered by events nested inside it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List[Any]] = []
    stack: List[Tuple[int, float]] = []         # (index into out, end)
    for name, start, dur in evs:
        end = start + dur
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:                               # nested in the stack's top
            out[stack[-1][0]][1] -= min(end, stack[-1][1]) - start
        out.append([name, dur])
        stack.append((len(out) - 1, end))
    return [(n, max(t, 0.0)) for n, t in out]


_HLO = re.compile(r"^%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\((.*)$", re.S)
_SHAPE = re.compile(r"\b([a-z]+\d+\w*)\[([\d,]+)\]")


def short_name(event_name: str) -> str:
    """An operation's name as the trace prints it is its whole HLO line.
    Kept: the result's name without its number, the opcode, and for a
    fusion its largest operand (which tells the matmuls of one kind of
    layer from another): ``fusion bf16[14336,4096]``,
    ``ragged_paged_attention_kernel custom-call``, ``all-gather-done``."""
    m = _HLO.match(event_name)
    if not m:
        return re.sub(r"\.\d+$", "", event_name.split(" = ", 1)[0]
                      .lstrip("%"))
    base = re.sub(r"\.\d+$", "", m.group(1))
    opcode = m.group(2)
    name = base if base == opcode else f"{base} {opcode}"
    if opcode == "fusion":
        shapes = _SHAPE.findall(m.group(3))
        if shapes:
            dtype, dims = max(shapes, key=lambda sh: math.prod(
                int(d) for d in sh[1].split(",")))
            name = f"{base} {dtype}[{dims}]"
    return name


def program_name(event_name: str) -> str:
    """``jit_ragged_decode_block(123)`` -> ``jit_ragged_decode_block``."""
    return event_name.split("(", 1)[0].strip()


@dataclasses.dataclass
class Reduced:
    chips: int
    window_s: float                 # traced extent
    busy_s: float                   # mean over chips
    program_s: Dict[str, float]     # per program, mean over chips
    program_runs: Dict[str, float]  # runs per program, mean over chips
    op_self_s: Dict[str, float]     # per operation name, mean over chips
    collective_exposed_s: float     # mean over chips
    gaps: List[Tuple[str, float]]   # (what ran next, seconds), all chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict[str, Any]:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:10]
        by: Dict[str, float] = {}
        for nxt, s in self.gaps:
            key = f"unattributed, before {nxt}"
            by[key] = by.get(key, 0.0) + s / self.chips
        gaps = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce_planes(planes: Planes) -> Reduced:
    if not planes:
        raise TraceError("the trace holds no TPU device plane")
    chips = len(planes)
    lo, hi = float("inf"), float("-inf")
    busy = exposed = 0.0
    prog_s: Dict[str, float] = {}
    prog_n: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for pname, lines in planes.items():
        modules = lines.get(MODULES_LINE, [])
        ops = lines.get(OPS_LINE) or modules
        if not ops:
            raise TraceError(f"plane {pname!r} holds no operation: lines "
                             f"{sorted(lines)}")
        merged = union([(s, s + d) for _, s, d in ops])
        lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        busy += sum(e - s for s, e in merged)
        for name, t in self_times(ops):
            name = short_name(name)     # operands must not name the op
            op_s[name] = op_s.get(name, 0.0) + t
            if COLLECTIVE.search(name):
                exposed += t
        for name, _, d in modules:
            key = program_name(name)
            prog_s[key] = prog_s.get(key, 0.0) + d
            prog_n[key] = prog_n.get(key, 0.0) + 1
        # a gap is charged to the program that runs after it; the host
        # side of it cannot be named until the program's own spans are in
        # the profiler's trace
        mods = sorted((s, s + d, program_name(n)) for n, s, d in modules)
        mod_starts = [m[0] for m in mods]
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            k = bisect.bisect_right(mod_starts, s1 + 1.0) - 1
            if k < 0 or mods[k][1] <= s1:
                nxt = "an operation outside any program"
            elif mods[k][0] >= e0:
                nxt = mods[k][2]
            else:
                nxt = f"the rest of {mods[k][2]}"
            gaps.append((nxt, (s1 - e0) / 1e9))
    ns = 1e9 * chips
    return Reduced(
        chips=chips, window_s=(hi - lo) / 1e9, busy_s=busy / ns,
        program_s={k: v / ns for k, v in prog_s.items()},
        program_runs={k: v / chips for k, v in prog_n.items()},
        op_self_s={k: v / ns for k, v in op_s.items()},
        collective_exposed_s=exposed / ns, gaps=gaps)


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce_planes(load_xplane(newest_xplane(trace_dir)))


if __name__ == "__main__":          # python benchmark/trace_reduce.py <dir>
    import sys

    path = newest_xplane(sys.argv[1])
    print("\n".join(describe(path)))
    r = reduce_dir(sys.argv[1])
    print({k: v for k, v in dataclasses.asdict(r).items()
           if k not in ("gaps", "op_self_s")})
    print(r.breakdown())
