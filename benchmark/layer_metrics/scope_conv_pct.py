"""The short-convolution mixer's share of the device's busy time: self
time of every operation under a ``conv`` module (in- and out-projection,
the gates, the taps, the per-slot state's gather and scatter) with the norm
that feeds it, over the summed self time of the traced slice
(``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "conv")
