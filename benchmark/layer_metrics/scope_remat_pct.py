"""Recomputation's share of a training step's device time: self time of
the operations whose scope path holds ``checkpoint/rematted_computation``
(the forward run again inside the backward pass), over the summed self time
of the traced steps (``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_phase", "remat")
