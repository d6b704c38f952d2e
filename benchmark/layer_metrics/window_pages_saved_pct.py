"""What giving pages back saves: 100 x (1 - pages the window layers' table
held / pages it would hold under the full layers' table), summed over the
``engine.dispatch`` spans of the traced slice (``window_pages_held`` and
``full_pages_held``: both allocators' own numbers at each dispatch; under ONE
table a window layer holds a page wherever a full layer does).  Pages, not
bytes: a window page is as many layers' rows as the model has window layers.
Spans without the counts give nothing to read."""
from benchmark.layer_metrics import trinity_trace


def read(ctx):
    spans = trinity_trace.pool_spans(ctx)
    full = sum(int(a.get("full_pages_held", 0)) for a in spans)
    if not full:
        return None
    return 100.0 * (1.0 - sum(int(a["window_pages_held"])
                              for a in spans) / full)
