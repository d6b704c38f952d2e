"""Latent attention's share of the device's busy time: the self time of its
projections (``q_a``, ``q_b``, ``kv_a``, ``o`` and the two low-rank norms),
of the two absorption products and of its two kernels, over the summed self
time of the traced slice.  The number that says the cell measures what it
was added for.  Narrower than ``scope_attn_pct``, which also counts the
block's norm and residual, RoPE and the scatter into the pool."""
from benchmark.layer_metrics import mla_trace


def read(ctx):
    got = mla_trace.rows(ctx)
    mine = mla_trace.seconds(
        ctx, mla_trace.PROJECTIONS + mla_trace.ABSORB
        + (mla_trace.CHUNK_KERNEL, mla_trace.DECODE_KERNEL))
    if not got or mine is None:
        return None
    return 100.0 * mine / sum(r[5] for r in got)
