"""What the indexer's and the chosen-token attention's readers share: the
device self time of the traced slice's operations by kind and sub-scope
(``mla_trace.rows``: ``scope_trace.join``) and the counts of the
``engine.dispatch`` spans.  Not a metric itself.

All of it is kind ``attn`` (module ``self_attn``).  Of PROMPT CHUNKS: the
scope ``index`` (the tiles' layout), the scope ``select`` (the shared-out
ties) and the ONE kernel ``index_select``, which scores and chooses since
the scores stay in VMEM from the product to the choice (``CHUNK_INDEX``
still lists ``index_scores``, the scoring kernel of its own that the parent
of that change had: no program of this tree has such a row, and the name
reads nothing); the kernel ``tokensel_chunk_attention``.  Of ONE-ROW
sequences (a decode block's rows, a fused tick's decoding sequences): the
scope ``index_row`` and the kernel ``row_index_scores`` (scores),
``select_row`` (``lax.top_k``) and ``tokensel_row_attention`` (the gather
by token and the attention).  The module ``indexer`` (the three
projections, the LayerNorm, the rotations) projects every row of a program:
a fused tick's is counted with its chunks, a decode block's with its rows.
A program without these scopes (every other model, or the parent of the PR
that added them) has no such rows, and its spans none of the counts, so
every reader here returns None."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import harness as H
from benchmark.layer_metrics import mla_trace, sala_trace

INDEXER = "indexer"
CHUNK_INDEX = ("index", "index_scores", "select", "index_select")
ROW_INDEX = ("index_row", "row_index_scores", "select_row")
CHUNK_ATTEND, ROW_ATTEND = "tokensel_chunk_attention", "tokensel_row_attention"
EVERYTHING = (INDEXER,) + CHUNK_INDEX + ROW_INDEX + (CHUNK_ATTEND, ROW_ATTEND)
# the names no other model's programs carry (``index`` and ``select`` are
# scopes other selections may enter): without one of these, nothing to read
OWN = tuple(s for s in EVERYTHING if s not in ("index", "select"))
PROGRAMS = sala_trace.PROGRAMS


def seconds(ctx: Dict[str, Any], subs, program: Optional[str] = None
            ) -> Optional[float]:
    """Self seconds of the attention operations whose sub-scope is one of
    ``subs``, in every program or in the one whose name holds ``program``
    (``fused`` / ``decode_block``); None where there is none.  The first
    call logs every sub-scope of kind ``attn`` on an earlier line
    (``keye_scopes``)."""
    got = mla_trace.rows(ctx)
    if got is None:
        return None
    if "_keye_logged" not in ctx:
        ctx["_keye_logged"] = True
        by_sub: Dict[str, float] = {}
        for r in got:
            if r[1] == "attn":
                key = f"{r[6]}/{r[2]}"
                by_sub[key] = by_sub.get(key, 0.0) + r[5]
        H.log({"keye_scopes": by_sub, "runs": {
            p: mla_trace.runs(ctx, p) for p in PROGRAMS}, "dispatches": {
            p: len(mla_trace.dispatches(ctx, p)) for p in PROGRAMS}})
    picked = [r[5] for r in got if r[1] == "attn" and r[2] in subs
              and (program is None or program in str(r[6]))]
    return sum(picked) if picked else None


def total(*parts: Optional[float]) -> Optional[float]:
    """The sum of the parts that are there; None where none is."""
    there = [p for p in parts if p]
    return sum(there) if there else None


needed = sala_trace.needed
bound_s = sala_trace.bound_s
one_row_steps = sala_trace.one_row_steps


def chunk_rows(program: str, d: Dict[str, Any]) -> int:
    """Rows of prompt chunks in a dispatch: a fused tick's rows that are
    not one-row sequences."""
    if program == "decode_block":
        return 0
    return int(d.get("index_rows", 0)) - int(d.get("one_row_seqs", 0))
