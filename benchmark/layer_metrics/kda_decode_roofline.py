"""The one-token step of Kimi Delta Attention as a share of its roofline,
which is memory: the state read and written once a live row a step at its
COUNTED size (``flops_solar.kda_decode``: 2 x 4,194,304 bytes a layer a row)
/ HBM bytes per second, over the device self time of ``kda_decode_step`` in
both programs (a decode block's ``rows x ticks``; the rows of a fused tick
that are their sequence's only one).  The kernel updates the state where it
lies, live rows only, and the state is stored as counted (heads along the
lanes, both dimensions whole lane tiles): what it adds to the bytes' own
time is a step's fixed cost and the vector unit's work, so the share cannot
pass 100 %.  A program without the kernel has no such operation, and the
reader returns None."""
from benchmark import flops_solar
from benchmark.layer_metrics import sala_trace

STEP = "kda_decode_step"


def read(ctx):
    secs = sala_trace.seconds(ctx, "linear", STEP)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: sala_trace.bound_s(
        ctx, flops_solar.kda_decode(
            cfg, sala_trace.one_row_steps(program, d))))
    return 100.0 * need / secs if need else None
