"""The matrix-state mixer's share of the device's busy time: self time of
every operation of kind ``linear`` with the norm that feeds it, over the
summed self time of the traced slice (``scope_trace``).  The kind is the
program's (``telemetry/device_scopes.py``), whichever rule updates the state:
a ``lightning_attn`` module (lightning attention: projections, QK-norm, RoPE,
``linear_chunk_scan``, ``linear_decode_step``, the rows' gathers to the tiles
and back, the output norm and gate) or a ``linear_attn`` module (the gated
delta rule: its projections and gate projections, the short convolution
INSIDE it with its carried rows, the L2 norms, ``delta_chunk_scan``,
``delta_decode_step``, the output norm and gate; the delta rule whose decay
is a vector a key channel is a ``linear_attn`` module too, with
``kda_chunk_scan`` and ``kda_decode_step``).  A program with neither module
has no such kind and the reader returns None."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "linear")
