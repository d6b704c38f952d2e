"""The chunk attention kernel at FOUR KV heads and a query group of seven as
a share of its roofline, window layers and full layers together: the work a
fused tick's prompt chunks need (``flops_smallthinker.attention``: ``QK^T``
and ``PV`` over the keys each row can REACH, ``min(p + 1, window)`` on a
window layer and ``p + 1`` on a full one, from the span's
``window_prefill_key_tokens`` and ``prefill_key_tokens``; q read and o written
once a layer; the larger of flops / peak bf16 FLOP/s and bytes / HBM bytes per
second), mean over the ticks dispatched in the slice x the ticks run in it,
over the device self time of ``paged_chunk_attention`` under both scopes.  The
kernel multiplies whole key blocks and whole row tiles, masked or not, and
lays every block out before it multiplies: none of that is needed work, so
the share cannot pass 100 %.  None for a program without the scopes."""
from benchmark import flops_smallthinker as F
from benchmark.layer_metrics import sala_trace, trinity_trace


def read(ctx):
    secs = trinity_trace.kernel_seconds(ctx, trinity_trace.CHUNK_KERNEL)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: sala_trace.bound_s(
        ctx, F.attention(cfg, F.key_tokens(d, "prefill"),
                         rows=float(d.get("prefill_tokens", 0))))
        if program == "fused" and int(d.get("prefill_tokens", 0)) > 0
        else 0.0)
    return 100.0 * need / secs if need else None
