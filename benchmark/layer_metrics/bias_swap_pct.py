"""How often the router's selection bias changes what a token takes:
``bias_swaps`` (tokens whose chosen set is not the top k of the scores
alone, summed over expert layers and ticks) over the routings THE ROUTER
made in the ``engine.moe`` spans of the traced slice, in percent.  A routing
is one token in one expert layer: ``moe_rows_routed`` / experts a token on
spans that carry it (a layer that holds a share of its router's experts:
``bias_swaps`` there counts live tokens over the WHOLE router, while
``moe_rows`` is what the held banks saw, an eighth of the copies on one chip
of eight), ``moe_rows`` / experts a token on spans that do not (a layer that
holds every expert its router names).  Zero would mean the bias is invisible
to every check; spans without the count (a softmax router) give nothing to
read.  Read in ``lfm2-reasoning-closed`` and ``glm-longdoc16k-closed`` (every
expert held).  Not listed for ``solar-longdoc16k-closed`` and
``trinity-longdoc32k-closed`` (one chip's share of eight): both
configurations start the bias at zero, so the share is 0 on every seed."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    spans = [r for r in moe_trace.spans(ctx) if "bias_swaps" in r]
    top_k = int(ctx["cell"].config["num_experts_per_tok"])
    routings = sum(int(r.get("moe_rows_routed", r["moe_rows"]))
                   for r in spans) / top_k
    if not routings:
        return None
    return 100.0 * sum(int(r["bias_swaps"]) for r in spans) / routings
