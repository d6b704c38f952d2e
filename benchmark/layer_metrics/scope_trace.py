"""Device time by layer kind: the trace's operations joined to the scope
paths the compiled programs carry.

Not a metric: the ``scope_*_pct`` families, ``device_unscoped_pct`` and the
run's ``device_scopes`` log line are read through it.

The trace names an operation of the ``XLA Ops`` line by its whole HLO line
(``%fusion.285 = bf16[32,14336]{...} fusion(...)``) and carries no
``op_name``.  The result's name (``fusion.285``) is the instruction's name
in the compiled module, and there the instruction carries the name stack it
was traced under: ``deepspeed_tpu/telemetry/device_scopes.py`` keeps every
dispatched program's jitted function and avals, compiles its text again
from them when asked (``tables()``: the executable in memory, nothing new)
and folds each instruction's path into ``(kind, sub, phase, via)``.  Here an
event finds its program by the ``XLA Modules`` event that encloses it
(``jit_ragged_decode_block(123)`` -> ``ragged_decode_block``), its scope by
its result's name among that program's variants (the one whose instruction
has the same opcode and shape), and its self time (``trace_reduce.
self_times``: a ``while`` or a ``call`` keeps only what its body's events
leave) goes to that scope.

- An event of a program the registry does not know (the eager
  ``jit__threefry_split``, ``jit__unstack``, ``jit_zero``) is kind ``rest``,
  sub its program, ``via="program"``.
- An event of a known program whose name no variant holds, or whose
  instruction found no scope within three hops, is ``unscoped``: what the
  names miss, ``device_unscoped_pct``.

Every share is of the summed self time of the slice's operations (the busy
time, where the line nests properly; the log line gives ``trace_reduce``'s
``busy_s`` beside it), pooled over chips.

With a program that has no ``telemetry.device_scopes`` (the parent of the
PR that added it), or whose registry holds no program, ``load`` returns
None and so does every reader.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness as H
from benchmark import trace_reduce as TR
from benchmark.layer_metrics import host_spans

UNSCOPED = "unscoped"
Row = Tuple[str, str, Optional[str], str, str, float, str]
#     short name, kind, sub, phase, via, seconds, program
# an event's name is its HLO line: result name, result type, opcode
_EVENT = re.compile(r"^%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", re.S)


def _program_of(modules: List[TR.Event]):
    """``start_ns -> program name without jit_ (or None)`` for one chip."""
    mods = sorted((s, s + d, TR.program_name(n)) for n, s, d in modules)
    starts = [m[0] for m in mods]

    def find(t: float) -> Optional[str]:
        k = bisect.bisect_right(starts, t) - 1
        if k < 0 or mods[k][1] <= t:
            return None
        name = mods[k][2]
        return name[4:] if name.startswith("jit_") else name

    return find


def join(planes: TR.Planes, tables: Dict[str, List[Any]]) -> List[Row]:
    """One row an operation event: its short name, scope and self seconds
    (divided by the number of chips, so sums are means over chips).
    ``tables``: ``{program: [variant, ...]}``, each variant a mapping
    ``instruction name -> (kind, sub, phase, via)`` with a ``signatures``
    mapping beside it (``device_scopes.ScopeTable``)."""
    from deepspeed_tpu.telemetry import device_scopes as DS

    rows: List[Row] = []
    for lines in planes.values():
        ops = lines.get(TR.OPS_LINE)
        if not ops:
            continue
        find = _program_of(lines.get(TR.MODULES_LINE, []))
        # ``self_times`` answers in this order
        ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
        for (line, start, _), (_, self_ns) in zip(ordered,
                                                  TR.self_times(ops)):
            s = self_ns / 1e9 / len(planes)
            short = TR.short_name(line)
            program = find(start)
            if program not in tables:
                program = program or "no program"
                rows.append((short, "rest", program, "-", "program", s,
                             program))
                continue
            m = _EVENT.match(line)
            scope = None if m is None else DS.lookup(
                tables[program], m.group(1),
                DS.signature(m.group(3), m.group(2)))
            if scope is None or scope[3] == "none":
                rows.append((short, UNSCOPED, None, "-", "none", s, program))
            else:
                rows.append((short,) + tuple(scope) + (s, program))
    return rows


def summarize(rows: List[Row]) -> Dict[str, Any]:
    """Seconds by kind (the ten kinds and ``unscoped``), by kind and
    sub-scope (top 15; and top 15 a program, since one label may name two
    programs' fusions), by phase, by ``via``; the unscoped operations (top
    5) and the twenty largest operations by short name, each with the
    scopes that hold most of it."""
    from deepspeed_tpu.telemetry.device_scopes import KINDS

    total = sum(r[5] for r in rows)
    by_kind = {k: 0.0 for k in KINDS + (UNSCOPED,)}
    by_sub: Dict[str, float] = {}
    by_program: Dict[str, float] = {}
    by_phase: Dict[str, float] = {}
    via: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    for short, kind, sub, phase, how, s, program in rows:
        by_kind[kind] = by_kind.get(kind, 0.0) + s
        key = f"{kind}/{sub}" if sub else kind
        by_sub[key] = by_sub.get(key, 0.0) + s
        by_program[f"{program}: {key}"] = by_program.get(
            f"{program}: {key}", 0.0) + s
        by_phase[phase] = by_phase.get(phase, 0.0) + s
        via[how] = via.get(how, 0.0) + s
        if kind == UNSCOPED:
            unscoped[short] = unscoped.get(short, 0.0) + s
        op = by_op.setdefault(short, {})
        op[f"{key} via {how}"] = op.get(f"{key} via {how}", 0.0) + s

    def top(d: Dict[str, float], n: int) -> List[List[Any]]:
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:n]]

    ops = sorted(by_op.items(), key=lambda kv: -sum(kv[1].values()))[:20]
    return {"total_s": total, "by_kind": by_kind,
            "by_kind_sub": top(by_sub, 15),
            "by_program_sub": top(by_program, 15), "by_phase": by_phase,
            "via": via, "unscoped_ops": top(unscoped, 5),
            "by_op": [[name, sum(d.values()), top(d, 2)]
                      for name, d in ops]}


def load(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The summary of THIS run's trace, or None; built and logged once."""
    if "_scope_trace" not in ctx:
        ctx["_scope_trace"] = _load(ctx)
    return ctx["_scope_trace"]


def _tables():
    """``(tables, seconds, compiles)`` from the program's registry; None
    where the program has none."""
    try:
        from deepspeed_tpu.telemetry import device_scopes
    except ImportError:
        return None
    from deepspeed_tpu.telemetry.compiles import count_compiles

    t = time.perf_counter()
    with count_compiles() as compiles:
        try:
            tables = device_scopes.tables()
        except Exception as e:      # the run's other metrics must still print
            H.log({"device_scopes": {"error": repr(e),
                                     "traceback": traceback.format_exc()}})
            return None
    return tables, time.perf_counter() - t, compiles.count


def _load(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    # ``host_spans.load`` has found and checked the run's trace directory
    # (the newest of the cell, its device extent the one the driver reduced)
    if host_spans.load(ctx) is None:
        return None
    got = _tables()
    if got is None or not got[0]:
        return None
    tables, tables_s, compiles = got
    dirs = glob.glob(os.path.join(H.OUT_DIR, f"trace-{ctx['cell'].name}-*"))
    planes = TR.load_xplane(TR.newest_xplane(max(dirs, key=os.path.getmtime)))
    rows = join(planes, tables)
    if not rows:
        return None
    out = summarize(rows)
    H.log({"device_scopes": {
        **out, "busy_s": ctx["trace"].busy_s,
        "programs": {p: len(v) for p, v in tables.items()},
        "tables_s": tables_s, "tables_compiles": compiles}})
    return out


def pct(ctx: Dict[str, Any], group: str, *keys: str) -> Optional[float]:
    """What the readers return: the seconds of ``keys`` in ``group``
    (``by_kind`` / ``by_phase``) as a percentage of the summed self time."""
    out = load(ctx)
    if out is None or not out["total_s"]:
        return None
    return 100.0 * sum(out[group].get(k, 0.0) for k in keys) / out["total_s"]
