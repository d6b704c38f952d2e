"""What the ``moe_*`` and ``hybrid_*`` readers share: the expert layer's
counts from the program's ``engine.moe`` spans, and the grouped matmul's
device time from the operations of the traced slice.  Not a metric itself.

**Spans.**  The serving engine brackets one ``engine.moe`` span a dispatch
of a program that holds expert layers, when the counts reach the host (on
the fetch the tick makes anyway): ``program`` (``fused`` or
``decode_block``), ``ticks`` (token steps in it), ``moe_rows`` (token
copies routed: live rows x experts a token, summed over layers and ticks),
``moe_experts_hit`` (experts with at least one row, summed the same way),
``moe_load_max`` (rows of the fullest expert of any layer and tick).  A
program without them (the parent of the PR that added them, or a dense
model) gives none, and every reader here returns None.

**Operations.**  An ``XLA Ops`` event is named by its whole HLO line, so
the grouped matmul is told by its NAME: the custom calls whose result is
named ``ragged-dot...`` (the Pallas kernel ``ragged-dot-gmm``; where a call
falls back, the v5e compiler's lowering of ``jax.lax.ragged_dot`` with its
small ``ragged-dot-metadata`` call).  The whole expert layer (routing,
dispatch, combine and their glue) is ``scope_moe_pct``'s to read, from the
compiled programs' module paths (``scope_trace``); the shape rule that told
it here until PR 42 could not be listed for LFM2 or GLM.
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


def kernel_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """Self seconds of the grouped matmul's custom calls in the slice."""
    ops = _device_ops(ctx)
    if ops is None:
        return None
    return sum(s for line, s in ops if KERNEL.search(line.split("(", 1)[0]))
