"""The FULL layers' attention as a share of the device's busy time: self time
of every operation under the scope ``attn_full`` over the summed self time of
the traced slice (``attn_window_share_pct`` reads the window layers').  None
for a program without the scope."""
from benchmark.layer_metrics import trinity_trace


def read(ctx):
    return trinity_trace.share_pct(ctx, "attn_full")
