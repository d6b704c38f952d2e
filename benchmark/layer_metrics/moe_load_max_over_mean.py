"""How uneven the router's load is: rows of the fullest expert
(``moe_load_max``, over the layers and ticks of a dispatch) over the mean
rows an expert gets in it (``moe_rows`` / (layers x ticks x experts)), mean
over the ``engine.moe`` spans of the slice.  1 is perfectly even; random
weights give about 3 at 4 rows an expert (a decode tick) and about 1.3 at
68 (a fused tick).  Real, skewed routing is what this is for."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    rows = [r for r in moe_trace.spans(ctx) if int(r["moe_rows"]) > 0]
    if not rows:
        return None
    cfg = ctx["cell"].config
    slots = int(cfg["num_hidden_layers"]) * int(cfg["num_experts"])
    return sum(int(r["moe_load_max"]) * slots * int(r["ticks"])
               / int(r["moe_rows"]) for r in rows) / len(rows)
