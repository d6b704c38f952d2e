"""Of every copy the router made, the share that went to an expert THIS CHIP
HOLDS: ``moe_rows`` (what the held banks multiplied) over ``moe_rows_routed``
(experts a token x live tokens x expert layers), summed over the
``engine.moe`` spans of the traced slice, in percent.  One chip of eight that
share a layer reads 12.5 under a uniform router; a seeded router skews.  It
tells the absent experts' share from imbalance among the held ones
(``hybrid_load_max_over_mean`` reads the second).  Spans without the count (a
layer that holds every expert its router names, or the parent of the PR that
added it) give nothing to read.  Read in ``solar-longdoc16k-closed`` and
``trinity-longdoc32k-closed``, one bare entry since PR 68."""
from benchmark.layer_metrics import moe_trace


def read(ctx):
    spans = [r for r in moe_trace.spans(ctx) if "moe_rows_routed" in r]
    routed = sum(int(r["moe_rows_routed"]) for r in spans)
    if not routed:
        return None
    return 100.0 * sum(int(r["moe_rows"]) for r in spans) / routed
