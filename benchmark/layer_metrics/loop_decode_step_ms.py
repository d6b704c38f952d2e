"""Device milliseconds per decode token step of a looped model, from the
decode program's runs that lie WHOLLY in the traced slice: their mean device
time / the token steps a block holds.  ``decode_step_device_ms`` divides the
program's device seconds by its runs; a block of this model is a quarter of
a second, the slice holds ten and cuts two, and the profiler records those
with the part it saw, so that reader is a tenth short here (31.0 for 34.5;
my chip run, PR 32).  Nothing to read where the dispatch spans carry no
``ut_steps`` or the slice holds no whole block."""
from benchmark.layer_metrics import loop_decode_roofline


def read(ctx):
    if not loop_decode_roofline.blocks(ctx):
        return None
    cfg = ctx["cell"].config
    runs = loop_decode_roofline.whole_runs(ctx, cfg["programs"]["decode"])
    if not runs:
        return None
    return (1e3 * sum(runs) / len(runs)
            / int(cfg["engine"]["decode_block_size"]))
