"""The indexer's, the selection's and the chosen-token attention's share of
the device's busy time: the self time of the module ``indexer``, of the
scopes ``index``, ``select``, ``index_row``, ``select_row`` and
``tokensel_row_attention`` and of the three kernels, over the summed self
time of the traced slice.  The number that says the cell measures what it
was added for.  Narrower than ``scope_attn_pct``, which also counts the
block's norm and residual, the q/k/v/o projections, RoPE and the scatter
into the pools."""
from benchmark.layer_metrics import keye_trace as kt
from benchmark.layer_metrics import mla_trace


def read(ctx):
    got = mla_trace.rows(ctx)
    mine = kt.seconds(ctx, kt.EVERYTHING)
    if not got or mine is None or not kt.seconds(ctx, kt.OWN):
        return None
    return 100.0 * mine / sum(r[5] for r in got)
