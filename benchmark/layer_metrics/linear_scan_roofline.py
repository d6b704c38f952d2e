"""The chunk-scan kernel of linear attention as a share of its roofline:
the work the chunked form needs for the ``prefill_tokens`` of a fused
tick's ``engine.dispatch`` span (``flops_sala.linear_scan``: four products
a chunk a head, q, k, v and o moved once; the larger of flops / peak bf16
FLOP/s and bytes / HBM bytes per second), over the device self time of
``linear_chunk_scan`` a run of the fused tick.  The kernel's state products
are float32 at highest precision, several MXU passes each, and its tiles
are padded to whole chunks: neither is needed work, so the share cannot
pass 100 %."""
from benchmark import flops_sala
from benchmark.layer_metrics import sala_trace


def read(ctx):
    secs = sala_trace.seconds(ctx, "linear", sala_trace.SCAN)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: (
        sala_trace.bound_s(ctx, flops_sala.linear_scan(
            cfg, int(d.get("prefill_tokens", 0))))
        if program == "fused" else 0.0))
    return 100.0 * need / secs if need else None
