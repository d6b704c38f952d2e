"""The backward pass's share of a training step's device time: self time
of the operations whose scope path holds ``transpose(jvp(`` and no
``rematted_computation`` (gradients proper, the recompute counted apart in
``scope_remat_pct``), over the summed self time of the traced steps
(``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_phase", "bwd")
