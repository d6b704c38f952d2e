"""What the ``mla_*`` readers share: the device self time of the traced
slice's operations by scope (``scope_trace.join``: kind, sub-scope and
program of every operation) and the counts of the ``engine.dispatch``
spans.  Not a metric itself.

Latent attention's operations are told by the scope paths the compiled
programs carry: kind ``attn`` with the sub-scope a projection's module name
(``q_a_proj``, ``q_b_proj``, ``kv_a_proj_with_mqa``, ``o_proj``; the two
low-rank norms between them), the ``absorb`` scope of the two absorption
products, or a kernel's own name (``latent_chunk_attention``,
``latent_decode_attention``).  A program without such scopes (a model with
per-head K/V, or the parent of the PR that added them) has no such rows,
and every reader here returns None."""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional

from benchmark import harness as H
from benchmark import trace_reduce as TR
from benchmark.layer_metrics import host_spans, scope_trace

CHUNK_KERNEL = "latent_chunk_attention"
DECODE_KERNEL = "latent_decode_attention"
PROJECTIONS = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
               "kv_a_layernorm", "o_proj")
ABSORB = ("absorb",)
# dispatch-span program -> role in the configuration's ``programs``
ROLES = {"fused": "prefill", "decode_block": "decode"}


def rows(ctx: Dict[str, Any]) -> Optional[List[scope_trace.Row]]:
    """Every operation of THIS run's trace with its scope, or None."""
    if "_mla_rows" not in ctx:
        ctx["_mla_rows"] = _rows(ctx)
    return ctx["_mla_rows"]


def _rows(ctx):
    if host_spans.load(ctx) is None:
        return None
    got = scope_trace._tables()
    if got is None or not got[0]:
        return None
    dirs = glob.glob(os.path.join(H.OUT_DIR, f"trace-{ctx['cell'].name}-*"))
    planes = TR.load_xplane(TR.newest_xplane(max(dirs, key=os.path.getmtime)))
    return scope_trace.join(planes, got[0]) or None


def seconds(ctx: Dict[str, Any], subs) -> Optional[float]:
    """Self seconds of the attention operations whose sub-scope is one of
    ``subs``; None where there is no such operation."""
    got = rows(ctx)
    if got is None:
        return None
    picked = [r[5] for r in got if r[1] == "attn" and r[2] in subs]
    return sum(picked) if picked else None


def dispatches(ctx: Dict[str, Any], program: str) -> List[Dict[str, Any]]:
    """The counts of the ``engine.dispatch`` spans of ``program`` (``fused``
    or ``decode_block``) in the traced slice."""
    ht = host_spans.load(ctx)
    if ht is None:
        return []
    return [e[3] for e in ht.spans("engine.dispatch")
            if e[3].get("program") == program]


def runs(ctx: Dict[str, Any], program: str) -> int:
    """Runs of the jitted program behind ``program`` in the slice."""
    name = ctx["cell"].config["programs"][ROLES[program]]
    return int(ctx["trace"].program_runs.get(name, 0))
