"""The mechanism's share of a FUSED TICK's device time: the self time, in
the fused tick, of the module ``indexer``, of the scopes ``index``,
``select``, ``index_row``, ``select_row`` and ``latent_select_row_attention``
and of the kernels ``index_select``, ``row_index_scores`` and
``latent_select_chunk_attention``, over the summed self time of the fused
tick's operations in the traced slice.  The number that says the cell
measures what it was added for.  Narrower than ``scope_attn_pct``, which
also counts the block's norm and residual, the latent projections, the
absorbing products, RoPE and the scatter into the pools, and counts the
decode blocks too."""
from benchmark.layer_metrics import glm5_trace as gt
from benchmark.layer_metrics import mla_trace


def read(ctx):
    got = mla_trace.rows(ctx)
    mine = gt.seconds(ctx, gt.EVERYTHING, "fused")
    if not got or mine is None or not gt.mine(ctx):
        return None
    tick = sum(r[5] for r in got if "fused" in str(r[6]))
    return 100.0 * mine / tick if tick else None
