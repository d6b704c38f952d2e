"""The head's share of the device's busy time: self time of the final
norm, the ``lm_head`` (or the tied embedding's ``attend``) and the
in-program sampling (scope ``sample``: keys, filters, the argmax or the
draw, the newest-token scatter), over the summed self time of the traced
slice (``scope_trace``)."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "head", "sample")
