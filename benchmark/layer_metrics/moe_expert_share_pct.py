"""The expert layer's share of the device's busy time: self time of routing
(router matmul, softmax, top-k), dispatch (sort, gather into expert-sorted
rows), the grouped expert matmuls and combine (gather back, weighted sum),
over the busy time of the traced slice.  Which operations those are:
``moe_trace.is_expert_op``.  The number that says whether the mechanism
does most of the work in a cell."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    if not moe_trace.spans(ctx):
        return None             # a program without expert layers
    ops = moe_trace.expert_ops(ctx)
    if not ops:
        return None
    return 100.0 * sum(ops.values()) / ctx["trace"].busy_s
