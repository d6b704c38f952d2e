"""The dense MLP's share of the device's busy time: self time of every
operation whose scope path lies under ``mlp`` / ``feed_forward`` where that
module is no expert layer (gate, up and down projections, the activation,
the norm that feeds them, and the weight prefetches ``slice-start`` /
``slice-done`` that the join gives to the fusion that reads them), over the
summed self time of the traced slice (``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "mlp")
