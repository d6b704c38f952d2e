"""The decode attention kernel at FOUR KV heads (a token pair a sublane tile)
as a share of its roofline, which is memory, window layers and full layers
together: K and V of every key a one-row sequence can REACH read once a KV
head (``flops_smallthinker.attention``: ``min(p + 1, window)`` keys on a
window layer, ``p + 1`` on a full one, from the spans'
``window_decode_key_tokens`` beside ``decode_key_tokens`` (a fused tick) or
``key_tokens`` (a decode block)), mean over a program's dispatches x its runs
in the slice, over the device self time of ``paged_decode_attention`` under
both scopes in both programs.  The kernel reads whole pages and whole groups
of them: the share cannot pass 100 %.  None for a program without the scopes."""
from benchmark import flops_smallthinker as F
from benchmark.layer_metrics import sala_trace, trinity_trace


def read(ctx):
    secs = trinity_trace.kernel_seconds(ctx, trinity_trace.DECODE_KERNEL)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: sala_trace.bound_s(
        ctx, F.attention(cfg, F.key_tokens(d, "decode"))))
    return 100.0 * need / secs if need else None
