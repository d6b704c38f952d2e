"""Attention of one-row sequences over their chosen tokens as a share of
its roofline, which is memory: a row reads its chosen tokens' K and V of
every KV head, 2,048 bytes a token a layer (``attend_decode_tokens`` of the
``engine.dispatch`` spans of both programs: ``min(t + 1, topk)`` a row;
``flops_keye.chosen_attention``) / HBM bytes per second, over the device
self time of the scope ``tokensel_row_attention`` (XLA's gather by token
and the attention over what it brought)."""
from benchmark import flops_keye
from benchmark.layer_metrics import keye_trace as kt


def read(ctx):
    secs = kt.seconds(ctx, (kt.ROW_ATTEND,))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = kt.needed(ctx, lambda program, d: kt.bound_s(
        ctx, flops_keye.chosen_attention(
            cfg, int(d.get("attend_decode_tokens", 0)),
            kt.one_row_steps(program, d), shared_keys=False)))
    return 100.0 * need / secs if need else None
