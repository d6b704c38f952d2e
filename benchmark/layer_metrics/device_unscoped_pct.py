"""What the names miss on the device side (the ``idle_unnamed_pct`` of
the busy time): self time of the operations of a registered program whose
instruction carries no scope of its own and found none within three hops of
users or operands, or whose name no compiled text of the program holds (a
stale table), over the summed self time of the traced slice
(``scope_trace``; the log line's ``unscoped_ops`` names them)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", scope_trace.UNSCOPED)
