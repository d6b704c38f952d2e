"""The decode step's share of its roofline, which is memory: (weight bytes
+ KV bytes of the live contexts) / HBM bytes per second, over the measured
device time of a decode token step.

The live contexts are an ESTIMATE until the program counts them: by the
engine's own records (``windows.request_spans``) a request decodes from the
end of its prefill to its finish, its context growing evenly from its
prompt to prompt + generated; the sum over decoding requests is averaged
over the traced slice.  Requests still prefilling hold KV too but a decode
step does not read it."""
from benchmark import flops
from benchmark.layer_metrics import windows as W
from benchmark.layer_metrics import decode_step_device_ms


def live_context_tokens(ctx, spans, points: int = 64) -> float:
    t0, t1 = W.trace_slice(ctx)
    total = 0.0
    for k in range(points):
        t = t0 + (t1 - t0) * (k + 0.5) / points
        total += sum(W.kv_tokens_held(s, t) for s in spans
                     if s["prefill_end"] <= t)
    return total / points


def read(ctx):
    step_ms = decode_step_device_ms.read(ctx)
    spans = W.spans(ctx)
    if step_ms is None or W.trace_slice(ctx) is None or spans is None:
        return None
    need = flops.decode_step_bytes(ctx["cell"].config,
                                   live_context_tokens(ctx, spans))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (step_ms / 1e3)
