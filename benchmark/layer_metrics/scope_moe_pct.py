"""The expert layer's share of the device's busy time, told by scope and
not by shape: self time of every operation under a module that holds
``moe.*`` scopes (``moe.route``: router matmul, scores, top-k;
``moe.dispatch``: sort and gather; ``moe.experts``: the grouped matmuls;
``moe.combine``: gather back and weighted sum) with the norm that feeds it,
over the summed self time of the traced slice (``scope_trace``).  The
whole layer in every architecture; ``hybrid_expert_share_pct`` beside it
reads the kernel alone."""
from benchmark.layer_metrics import scope_trace


def read(ctx):
    return scope_trace.pct(ctx, "by_kind", "moe")
