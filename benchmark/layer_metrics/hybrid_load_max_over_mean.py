"""How uneven the router's load is in a hybrid model: rows of the fullest
expert (``moe_load_max``) over the mean rows an expert gets in a dispatch
(``moe_rows`` / (EXPERT layers x ticks x experts)), mean over the
``engine.moe`` spans of the slice.  ``moe_load_max_over_mean`` with the
layers that HAVE experts (``num_hidden_layers - num_dense_layers``): that
reader divides by every layer."""
from benchmark import flops_hybrid
from benchmark.layer_metrics import moe_trace


def read(ctx):
    rows = [r for r in moe_trace.spans(ctx) if int(r["moe_rows"]) > 0]
    if not rows:
        return None
    cfg = ctx["cell"].config
    slots = flops_hybrid.expert_layers(cfg) * int(cfg["num_experts"])
    return sum(int(r["moe_load_max"]) * slots * int(r["ticks"])
               / int(r["moe_rows"]) for r in rows) / len(rows)
