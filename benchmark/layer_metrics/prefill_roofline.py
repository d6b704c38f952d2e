"""The SplitFuse tick's share of its roofline, which is compute: FLOPs the
prompt tokens prefilled in the traced slice need / peak bf16 FLOP/s, over
the device time of the fused tick's runs in the slice.

Which prompt tokens fell into the slice is an ESTIMATE until the program
counts them: the engine's own records say when each request was admitted
and when its prefill ended (``windows.request_spans``), and its prompt is
taken to fill evenly between the two, a query at fraction ``f`` of the
prompt seeing ``f * prompt`` keys (causal).  The decode rows that ride in
the same ticks, and the head, are left out of the needed work."""
from benchmark import flops
from benchmark.layer_metrics import windows as W


def read(ctx):
    secs = W.program_seconds(ctx, "prefill")
    sl = W.trace_slice(ctx)
    spans = W.spans(ctx)
    if not secs or sl is None or spans is None:
        return None
    need = 0.0
    for s in spans:
        length = max(s["prefill_end"] - s["admit"], 1e-9)
        f0 = (max(s["admit"], sl[0]) - s["admit"]) / length
        f1 = (min(s["prefill_end"], sl[1]) - s["admit"]) / length
        if f1 > f0:
            need += flops.prefill_flops(
                ctx["cell"].config, (f1 - f0) * s["prompt_tokens"],
                0.5 * (f0 + f1) * s["prompt_tokens"])
    return 100.0 * (need / ctx["peaks"]["bf16_flops_per_s"]) / secs
