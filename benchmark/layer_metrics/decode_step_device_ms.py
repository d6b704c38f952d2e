"""Device milliseconds per decode token step: device time of the decode
block's runs in the traced slice / (runs x token steps a block holds)."""
from benchmark.layer_metrics import windows as W


def read(ctx):
    secs = W.program_seconds(ctx, "decode")
    if not secs:
        return None
    cfg = ctx["cell"].config
    runs = ctx["trace"].program_runs[cfg["programs"]["decode"]]
    return 1e3 * secs / (runs * int(cfg["engine"]["decode_block_size"]))
