"""Of the keys a dense pass would read for the queries with more than
``topk`` keys to choose from, the share they attend: ``sel_key_tokens`` /
``sel_visible_tokens`` summed over the ``engine.dispatch`` spans of the
traced slice.  ``topk`` 2,048 of 12k to 32k visible keys: about 8-17 %;
100 would mean the selection did not run.  Spans without the counts (every
other model, or the parent of the PR that added them) give nothing to
read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    spans = [e[3] for e in ht.spans("engine.dispatch")
             if "sel_visible_tokens" in e[3]]
    seen = sum(int(a["sel_visible_tokens"]) for a in spans)
    if not seen:
        return None
    return 100.0 * sum(int(a["sel_key_tokens"]) for a in spans) / seen
