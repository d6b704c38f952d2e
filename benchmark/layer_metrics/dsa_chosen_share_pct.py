"""Of the (query, key) pairs the indexer scores, the share the heads
attend: ``attend_prefill_tokens + attend_decode_tokens`` (``min(t + 1,
index_topk)`` a query) over ``index_visible_tokens`` (``t + 1`` a query),
summed over the ``engine.dispatch`` spans of the traced slice, of a program
that carries the chosen-latent names.  ``index_topk`` 2,048 of 8k to 16k
visible keys: about 15-25 %; 100 would mean the selection did not run.
Spans without the counts (every other model, or the parent of the PR that
added them) give nothing to read."""
from benchmark.layer_metrics import glm5_trace as gt
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None or not gt.mine(ctx):
        return None
    spans = [e[3] for e in ht.spans("engine.dispatch")
             if "index_visible_tokens" in e[3]]
    seen = sum(int(a["index_visible_tokens"]) for a in spans)
    if not seen:
        return None
    return 100.0 * sum(int(a.get("attend_prefill_tokens", 0))
                       + int(a.get("attend_decode_tokens", 0))
                       for a in spans) / seen
