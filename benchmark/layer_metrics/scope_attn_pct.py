"""Attention's share of the device's busy time: self time of every
operation whose scope path lies under an attention module (``self_attn``:
the q/k/v/o projections, RoPE, QK-norm, the K/V scatter, the Pallas kernels
``paged_decode_attention`` / ``paged_chunk_attention`` / ``flash_*``) or is
the block's norm or residual that feeds it, over the summed self time of the
traced slice.  ``scope_trace`` joins the trace's operations to the compiled
programs' scope paths; None where the program keeps no such registry."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "attn")
