"""What the four ``moe_*`` readers share: the expert layer's counts from the
program's ``engine.moe`` spans, and the expert layer's device time from the
operations of the traced slice.  Not a metric itself.

**Spans.**  The serving engine brackets one ``engine.moe`` span a dispatch
of a program that holds expert layers, when the counts reach the host (on
the fetch the tick makes anyway): ``program`` (``fused`` or
``decode_block``), ``ticks`` (token steps in it), ``moe_rows`` (token
copies routed: live rows x experts a token, summed over layers and ticks),
``moe_experts_hit`` (experts with at least one row, summed the same way),
``moe_load_max`` (rows of the fullest expert of any layer and tick).  A
program without them (the parent of the PR that added them, or a dense
model) gives none, and every reader here returns None.

**Operations.**  An ``XLA Ops`` event is named by its whole HLO line and
carries no scope (PERF.md, section 7), so the expert layer's operations are
told by what the line says:

- the grouped matmul itself: the v5e compiler lowers ``jax.lax.ragged_dot``
  to custom calls whose result is named ``ragged-dot...`` (the matmul and a
  small ``ragged-dot-metadata`` call that turns group sizes into tiles);
- routing, dispatch and combine: every operation whose line holds a shape
  with a dimension equal to the routed copies of one of the two programs
  (``rows x experts a token``: the sorted buffer, the sort, the gathers,
  the weighted sum), or one of the exact shapes ``[rows, experts]`` in a
  float type (router logits and gates), ``[rows, experts a token]`` (top-k)
  or ``s32[experts]`` (group sizes).

The second rule reads shapes, not names: another operation with such a
shape would be counted too.  The first reading on the chip counted the
paged attention kernel, whose page table is ``s32[32,64]`` (32 sequences x
64 pages, as 32 rows x 64 experts), hence the float type; at the sizes of
``olmoe-1b-7b-serve`` (copies 256 and 4352, 64 experts, top 8) nothing
else is caught (my chip run, PR 28: ``expert_ops`` lists what is).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness as H
from benchmark import trace_reduce as TR
from benchmark.layer_metrics import host_spans

SPAN = "engine.moe"
KERNEL = re.compile(r"ragged-dot")
_SHAPE = re.compile(r"\b([a-z]+\d+\w*)\[([\d,]*)\]")


def spans(ctx: Dict[str, Any], program: Optional[str] = None
          ) -> List[Dict[str, Any]]:
    """The counts of the ``engine.moe`` spans of the traced slice, of one
    ``program`` or of all."""
    ht = host_spans.load(ctx)
    if ht is None:
        return []
    rows = [e[3] for e in ht.spans(SPAN)]
    return [r for r in rows
            if program is None or r.get("program") == program]


def _rows_of(config: Dict[str, Any]) -> Tuple[int, int]:
    """Token rows of the decode tick and of the fused tick."""
    eng = config["engine"]
    seqs = int(eng["max_seqs"])
    return seqs, seqs + int(eng["prefill_chunk"])


def is_expert_op(hlo_line: str, config: Dict[str, Any]) -> bool:
    if KERNEL.search(hlo_line.split("(", 1)[0]):
        return True
    experts = int(config["num_experts"])
    top_k = int(config["num_experts_per_tok"])
    rows = _rows_of(config)
    copies = {t * top_k for t in rows}
    for dtype, m in _SHAPE.findall(hlo_line):
        dims = tuple(int(d) for d in m.split(",") if d)
        if copies & set(dims):
            return True
        if len(dims) == 2 and dims[0] in rows and (
                dims[1] == top_k or
                (dims[1] == experts and dtype[0] in "fb")):
            return True
        if dims == (experts,) and dtype == "s32":
            return True
    return False


def _device_ops(ctx: Dict[str, Any]) -> Optional[List[Tuple[str, float]]]:
    """``(whole HLO line, self seconds)`` of every device operation of THIS
    run's trace, mean over chips."""
    if "_moe_device_ops" in ctx:
        return ctx["_moe_device_ops"]
    ctx["_moe_device_ops"] = out = _load_ops(ctx)
    return out


def _load_ops(ctx):
    # ``host_spans.load`` has found and checked the run's trace directory
    # (the newest of the cell, its device extent the one the driver reduced)
    if host_spans.load(ctx) is None:
        return None
    dirs = glob.glob(os.path.join(H.OUT_DIR, f"trace-{ctx['cell'].name}-*"))
    planes = TR.load_xplane(TR.newest_xplane(max(dirs, key=os.path.getmtime)))
    out: List[Tuple[str, float]] = []
    for lines in planes.values():
        ops = lines.get(TR.OPS_LINE)
        if not ops:
            return None
        out.extend((n, t / 1e9 / len(planes)) for n, t in TR.self_times(ops))
    return out


def expert_ops(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Self seconds of the expert layer's operations in the slice, by short
    name (``trace_reduce.short_name``), kernel and the rest alike."""
    ops = _device_ops(ctx)
    if ops is None:
        return None
    out: Dict[str, float] = {}
    for line, s in ops:
        if is_expert_op(line, ctx["cell"].config):
            key = TR.short_name(line)
            out[key] = out.get(key, 0.0) + s
    return out


def kernel_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """Self seconds of the grouped matmul's custom calls in the slice."""
    ops = _device_ops(ctx)
    if ops is None:
        return None
    return sum(s for line, s in ops if KERNEL.search(line.split("(", 1)[0]))
