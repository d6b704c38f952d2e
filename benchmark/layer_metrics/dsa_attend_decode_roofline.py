"""Latent attention of one-row sequences over their chosen rows as a share
of its roofline, which is memory: a row reads its chosen tokens' ``[c |
k_r]``, 1,152 bytes a token a layer and ONE row for all 64 heads
(``attend_decode_tokens`` of the ``engine.dispatch`` spans of both programs:
``min(t + 1, index_topk)`` a row; ``flops_glm5.chosen_attention``) / HBM
bytes per second, over the device self time of the scope
``latent_select_row_attention`` (the row's absorbed queries, XLA's gather by
token and the attention over what it brought)."""
from benchmark import flops_glm5
from benchmark.layer_metrics import glm5_trace as gt


def read(ctx):
    secs = gt.seconds(ctx, (gt.ROW_ATTEND,))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = gt.needed(ctx, lambda program, d: gt.bound_s(
        ctx, flops_glm5.chosen_attention(
            cfg, int(d.get("attend_decode_tokens", 0)),
            gt.one_row_steps(program, d), shared_keys=False)))
    return 100.0 * need / secs if need else None
