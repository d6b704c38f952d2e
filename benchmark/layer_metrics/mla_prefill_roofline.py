"""The prompt-chunk kernel of latent attention as a share of its roofline,
which is compute: the FLOPs of the ABSORBED form it runs
(``flops_mla.chunk_attention_flops``: 2 x (576 + 512) a query-key pair a
head, over the layers) for the ``prefill_key_tokens`` of a fused tick's
``engine.dispatch`` span, / peak bf16 FLOP/s, over the device self time of
``latent_chunk_attention`` a run of the fused tick.  Both sides per tick, as
``prefill_roofline_counted``: mean needed work of the ticks DISPATCHED in
the slice over mean kernel time of the ticks RUN in it.  The kernel
multiplies the pool's padded rows (640 for 576) and whole masked tiles on
the diagonal; neither is needed work, so the share cannot pass 100 %."""
from benchmark import flops_mla
from benchmark.layer_metrics import mla_trace


def read(ctx):
    secs = mla_trace.seconds(ctx, (mla_trace.CHUNK_KERNEL,))
    ticks = [t for t in mla_trace.dispatches(ctx, "fused")
             if int(t.get("prefill_tokens", 0)) > 0]
    runs = mla_trace.runs(ctx, "fused") if secs else 0
    if not secs or not ticks or not runs:
        return None
    cfg = ctx["cell"].config
    need = sum(flops_mla.chunk_attention_flops(
        cfg, int(t["prefill_key_tokens"])) for t in ticks) / len(ticks)
    return 100.0 * (need / ctx["peaks"]["bf16_flops_per_s"]) / (secs / runs)
