"""The two Kimi-Delta-Attention kernels' share of the device's busy time: the
self time of ``kda_chunk_scan`` and ``kda_decode_step`` over the summed self
time of the traced slice.  The number that says the cell measures what it
was added for.  Narrower than ``scope_linear_pct``, which also counts the
mixer's projections, its convolution, the L2 norms and gates, the tile
layout's gathers, the output norm and gate and the block's norm.  A program
without the kernels has no such operation, and the reader returns None."""
from benchmark.layer_metrics import mla_trace, sala_trace
from benchmark.layer_metrics.kda_decode_roofline import STEP
from benchmark.layer_metrics.kda_scan_roofline import SCAN


def read(ctx):
    got = mla_trace.rows(ctx)
    mine = [sala_trace.seconds(ctx, "linear", name) for name in (SCAN, STEP)]
    if not got or not any(mine):
        return None
    return 100.0 * sum(s or 0.0 for s in mine) / sum(r[5] for r in got)
