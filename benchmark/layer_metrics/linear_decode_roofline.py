"""The one-token step of linear attention as a share of its roofline,
which is memory: the state read and written once a live row a step
(``flops_sala.linear_decode``: 2 x 2 MiB a layer a row) / HBM bytes per
second, over the device self time of ``linear_decode_step`` in both
programs (a decode block's ``rows x ticks``; the rows of a fused tick that
are their sequence's only one).  The kernel updates the state where it
lies, live rows only, a grid step a row a block of eight heads: what it
adds to the bytes' own time is a step's fixed cost, so the share cannot
pass 100 %."""
from benchmark import flops_sala
from benchmark.layer_metrics import sala_trace


def read(ctx):
    secs = sala_trace.seconds(ctx, "linear", sala_trace.STEP)
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = sala_trace.needed(ctx, lambda program, d: sala_trace.bound_s(
        ctx, flops_sala.linear_decode(
            cfg, sala_trace.one_row_steps(program, d))))
    return 100.0 * need / secs if need else None
