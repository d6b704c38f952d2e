"""Attention of prompt chunks over their CHOSEN keys as a share of its
roofline, which is compute: ``4 d`` flops a chosen (query, key) pair a
query head (``attend_prefill_tokens`` of the fused ticks'
``engine.dispatch`` spans: ``min(t + 1, topk)`` a row, NOT the keys it sees;
``flops_keye.chosen_attention``) / peak bf16 FLOP/s, over the device self
time of the kernel ``tokensel_chunk_attention``.  The kernel is a dense
pass under the per-(row, key) mask (the union of what a tile's 128 rows
choose is every key), so at 24k it multiplies twelve times the pairs the
equations attend: the share says what a form that gathers and does not
mask would gain."""
from benchmark import flops_keye
from benchmark.layer_metrics import keye_trace as kt


def read(ctx):
    secs = kt.seconds(ctx, (kt.CHUNK_ATTEND,))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = kt.needed(ctx, lambda program, d: kt.bound_s(
        ctx, flops_keye.chosen_attention(
            cfg, int(d.get("attend_prefill_tokens", 0)),
            kt.chunk_rows(program, d), shared_keys=True)))
    return 100.0 * need / secs if need else None
