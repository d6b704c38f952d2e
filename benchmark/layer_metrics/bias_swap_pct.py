"""How often the router's selection bias changes what a token takes:
``bias_swaps`` (tokens whose chosen set is not the top k of the scores
alone, summed over expert layers and ticks) over the routings of the
``engine.moe`` spans of the traced slice (``moe_rows`` / experts a token),
in percent.  Zero would mean the bias is invisible to every check; spans
without the count (a softmax router) give nothing to read."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    spans = [r for r in moe_trace.spans(ctx) if "bias_swaps" in r]
    top_k = int(ctx["cell"].config["num_experts_per_tok"])
    routings = sum(int(r["moe_rows"]) for r in spans) / top_k
    if not routings:
        return None
    return 100.0 * sum(int(r["bias_swaps"]) for r in spans) / routings
