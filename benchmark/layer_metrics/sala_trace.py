"""What the linear-attention and block-selection readers share: the device
self time of the traced slice's operations by kind and sub-scope
(``mla_trace.rows``: ``scope_trace.join``) and the counts of the
``engine.dispatch`` spans.  Not a metric itself.

The four kernels are told by their own names: ``linear_chunk_scan`` and
``linear_decode_step`` under kind ``linear`` (module ``lightning_attn``),
``sparse_chunk_attention`` and ``sparse_decode_attention`` under kind
``attn`` (module ``self_attn``).  A program without them (every other
model, or the parent of the PR that added them) has no such rows, and its
spans none of the counts, so every reader here returns None."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import harness as H
from benchmark.layer_metrics import mla_trace

SCAN, STEP = "linear_chunk_scan", "linear_decode_step"
SPARSE_CHUNK, SPARSE_DECODE = ("sparse_chunk_attention",
                               "sparse_decode_attention")
PROGRAMS = ("fused", "decode_block")


def seconds(ctx: Dict[str, Any], kind: str, sub: str) -> Optional[float]:
    """Self seconds of the operations of ``kind`` whose sub-scope is
    ``sub``; None where there is none.  The first call logs every
    sub-scope of the two mixers on an earlier line (``sala_scopes``)."""
    got = mla_trace.rows(ctx)
    if got is None:
        return None
    if "_sala_logged" not in ctx:
        ctx["_sala_logged"] = True
        by_sub: Dict[str, float] = {}
        for r in got:
            if r[1] in ("linear", "attn"):
                key = f"{r[1]}/{r[2]}"
                by_sub[key] = by_sub.get(key, 0.0) + r[5]
        H.log({"sala_scopes": by_sub, "runs": {
            p: mla_trace.runs(ctx, p) for p in PROGRAMS}, "dispatches": {
            p: len(mla_trace.dispatches(ctx, p)) for p in PROGRAMS}})
    picked = [r[5] for r in got if r[1] == kind and r[2] == sub]
    return sum(picked) if picked else None


def needed(ctx: Dict[str, Any], of) -> float:
    """``of(program, span counts)`` (seconds of needed work a dispatch),
    mean over a program's dispatches in the slice x its runs in the slice,
    summed over the two programs."""
    total = 0.0
    for program in PROGRAMS:
        spans: List[Dict[str, Any]] = mla_trace.dispatches(ctx, program)
        runs = mla_trace.runs(ctx, program)
        if spans and runs:
            total += runs * sum(of(program, d) for d in spans) / len(spans)
    return total


def bound_s(ctx: Dict[str, Any], work: Dict[str, float]) -> float:
    """The least seconds the chip could take for ``work``."""
    return max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
               work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])


def one_row_steps(program: str, d: Dict[str, Any]) -> int:
    """Sequences that took the one-token kernels in a dispatch, times its
    token steps: a decode block's ``rows x ticks``, a fused tick's
    ``one_row_seqs``."""
    if program == "decode_block":
        return int(d["rows"]) * int(d["ticks"])
    return int(d.get("one_row_seqs", 0))
