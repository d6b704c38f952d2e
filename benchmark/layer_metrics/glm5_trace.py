"""What the ``dsa_*`` readers share: the names GLM-5's indexer and its
latent attention over chosen rows carry in a device trace, over
``keye_trace``'s sums (``mla_trace.rows``: the device self time of the
traced slice's operations by kind and sub-scope) and the counts of the
``engine.dispatch`` spans.  Not a metric itself.

All of it is kind ``attn`` (module ``self_attn``).  Of PROMPT CHUNKS: the
scope ``index`` (the tiles' layout), the scope ``select`` and the ONE kernel
``index_select``, which scores and chooses; the kernel
``latent_select_chunk_attention``.  Of ONE-ROW sequences (a decode block's
rows, a fused tick's decoding sequences): the scope ``index_row`` and the
kernel ``row_index_scores`` (scores), ``select_row`` (``lax.top_k``) and
``latent_select_row_attention`` (the row's absorbed queries, the gather by
token and the attention).  The module ``indexer`` (the three projections,
the LayerNorm, the rotations) projects every row of a program: a fused
tick's is counted with its chunks, a decode block's with its rows.  A
program without the two names of its own (every other model, or the parent
of the PR that added them) has no such rows, and every reader here returns
None."""
from __future__ import annotations

from benchmark.layer_metrics import keye_trace as kt

INDEXER = kt.INDEXER
CHUNK_INDEX = ("index", "select", "index_select")
ROW_INDEX = kt.ROW_INDEX
CHUNK_ATTEND = "latent_select_chunk_attention"
ROW_ATTEND = "latent_select_row_attention"
EVERYTHING = (INDEXER,) + CHUNK_INDEX + ROW_INDEX + (CHUNK_ATTEND, ROW_ATTEND)
# the names no other model's programs carry
OWN = (CHUNK_ATTEND, ROW_ATTEND)

seconds, total = kt.seconds, kt.total
needed, bound_s = kt.needed, kt.bound_s
one_row_steps, chunk_rows = kt.one_row_steps, kt.chunk_rows


def mine(ctx) -> bool:
    """Does the traced program carry this mechanism's own names?"""
    return bool(seconds(ctx, OWN))
