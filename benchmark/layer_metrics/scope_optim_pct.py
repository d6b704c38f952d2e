"""The optimizer's and ZeRO's share of a training step's device time:
self time of the operations under the scopes ``optim`` (gradient norm,
clipping, the optimizer's update, the parameter update) and ``zero.fetch``
(the fetch of host-resident or sharded state), over the summed self time of
the traced steps (``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "optim", "zero")
