"""Latent attention of prompt chunks over their CHOSEN rows as a share of
its roofline, which is compute: 2,176 flops a chosen (query, key) pair a
head in the absorbed form (``attend_prefill_tokens`` of the fused ticks'
``engine.dispatch`` spans: ``min(t + 1, index_topk)`` a row, NOT the keys it
sees; ``flops_glm5.chosen_attention``) / peak bf16 FLOP/s, over the device
self time of the kernel ``latent_select_chunk_attention``.  The kernel is a
dense pass under the per-(row, key) bias (the union of what a tile's 128
rows choose is every key), so at 12k it multiplies six times the pairs the
equations attend: the share says what a form that gathers and does not
mask would gain."""
from benchmark import flops_glm5
from benchmark.layer_metrics import glm5_trace as gt


def read(ctx):
    secs = gt.seconds(ctx, (gt.CHUNK_ATTEND,))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = gt.needed(ctx, lambda program, d: gt.bound_s(
        ctx, flops_glm5.chosen_attention(
            cfg, int(d.get("attend_prefill_tokens", 0)),
            gt.chunk_rows(program, d), shared_keys=True)))
    return 100.0 * need / secs if need else None
