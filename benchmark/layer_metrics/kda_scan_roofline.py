"""The chunk-scan kernel of Kimi Delta Attention as a share of its roofline:
the work the chunked form needs for the ``prefill_tokens`` of a fused tick's
``engine.dispatch`` span (``flops_solar.kda_scan``: the products of a chunk a
head and a solve by forward substitution; q, k, v, o and ``g`` at 128 a head
moved once; the larger of flops / peak bf16 FLOP/s and bytes / HBM bytes per
second), over the device self time of ``kda_chunk_scan`` a run of the fused
tick.  The kernel's products are float32 at highest precision, several MXU
passes each, ``P`` and ``R`` are built level by level (seven masked products
where the triangle needs half of one), the solve by doubling, and its tiles
are padded to whole chunks: none of that is needed work, so the share cannot
pass 100 %.  A program without the kernel (every other model, or the parent
of the PR that added it) has no such operation, and the reader returns
None."""
from benchmark import flops_solar
from benchmark.layer_metrics import sala_trace

SCAN = "kda_chunk_scan"


def read(ctx):
    secs = sala_trace.seconds(ctx, "linear", SCAN)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: (
        sala_trace.bound_s(ctx, flops_solar.kda_scan(
            cfg, int(d.get("prefill_tokens", 0))))
        if program == "fused" else 0.0))
    return 100.0 * need / secs if need else None
