"""The prompt-chunk kernel of block-selecting attention as a share of its
roofline, which is compute: the flops of the (query, key) pairs the layer
equations attend (``attend_prefill_tokens`` of a fused tick's
``engine.dispatch`` span: every earlier key before ``dense_len``, the
CHOSEN blocks' keys from there on; ``flops_sala.sparse_attention``) / peak
bf16 FLOP/s, over the device self time of ``sparse_chunk_attention`` a run
of the fused tick.  The kernel is a dense pass under the mask: the keys it
multiplies and masks away are not needed work, so the share cannot pass
100 % and falls as contexts pass ``dense_len``."""
from benchmark import flops_sala
from benchmark.layer_metrics import sala_trace


def read(ctx):
    secs = sala_trace.seconds(ctx, "attn", sala_trace.SPARSE_CHUNK)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: (
        flops_sala.sparse_attention(
            cfg, int(d.get("attend_prefill_tokens", 0)), 0)["flops"]
        / ctx["peaks"]["bf16_flops_per_s"] if program == "fused" else 0.0))
    return 100.0 * need / secs if need else None
