"""The WINDOW layers' attention as a share of the device's busy time: self
time of every operation under the scope ``attn_window`` (projections, head
norms, rotary embedding, K/V scatter, both kernels, the gate, ``o_proj``)
over the summed self time of the traced slice.  With ``attn_full_share_pct``
it splits ``scope_attn_pct`` (which also counts the blocks' norms and
residuals) by layer kind.  None for a program without the scope."""
from benchmark.layer_metrics import trinity_trace


def read(ctx):
    return trinity_trace.share_pct(ctx, "attn_window")
