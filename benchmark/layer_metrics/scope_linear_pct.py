"""The linear-attention mixer's share of the device's busy time: self time
of every operation under a ``lightning_attn`` module (projections, QK-norm,
RoPE, the chunk-scan kernel, the decode step, the state's gather and
scatter, the output norm and gate) with the norm that feeds it, over the
summed self time of the traced slice (``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "linear")
