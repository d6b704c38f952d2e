"""The chunk-scan kernel of the gated delta rule as a share of its roofline:
the work the chunked form needs for the ``prefill_tokens`` of a fused tick's
``engine.dispatch`` span (``flops_olmo_hybrid.delta_scan``: the products of a
chunk a head and a solve by forward substitution, q, k, v and o moved once;
the larger of flops / peak bf16 FLOP/s and bytes / HBM bytes per second),
over the device self time of ``delta_chunk_scan`` a run of the fused tick.
The kernel's state products and its solve are float32 at highest precision,
several MXU passes each, the solve is built by doubling (twelve products of
C x C where substitution needs a third of one), and its tiles are padded to
whole chunks: none of that is needed work, so the share cannot pass 100 %.
A program without the kernel (every other model, or the parent of the PR
that added it) has no such operation, and the reader returns None."""
from benchmark import flops_olmo_hybrid
from benchmark.layer_metrics import sala_trace

SCAN = "delta_chunk_scan"


def read(ctx):
    secs = sala_trace.seconds(ctx, "linear", SCAN)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: (
        sala_trace.bound_s(ctx, flops_olmo_hybrid.delta_scan(
            cfg, int(d.get("prefill_tokens", 0))))
        if program == "fused" else 0.0))
    return 100.0 * need / secs if need else None
