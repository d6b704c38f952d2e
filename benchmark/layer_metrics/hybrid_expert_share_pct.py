"""The grouped expert matmul's share of the device's busy time in a hybrid
model: self time of the ``ragged-dot...`` custom calls over the busy time
of the traced slice.  The kernel alone, told by its NAME; routing,
dispatch and combine with it are ``scope_moe_pct``."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    if not moe_trace.spans(ctx):
        return None             # a program without expert layers
    kernel_s = moe_trace.kernel_seconds(ctx)
    if not kernel_s:
        return None
    return 100.0 * kernel_s / ctx["trace"].busy_s
