"""Of the keys a dense pass would read for the queries at or past
``dense_len``, the share their chosen blocks hold: ``sparse_key_tokens`` /
``sparse_visible_tokens`` summed over the ``engine.dispatch`` spans of the
traced slice.  About 50 % at 8k and 25 % at 16k; 100 would mean the
selection did not run.  Spans without the counts (every other model, or the
parent of the PR that added them) give nothing to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    spans = [e[3] for e in ht.spans("engine.dispatch")
             if "sparse_visible_tokens" in e[3]]
    seen = sum(int(a["sparse_visible_tokens"]) for a in spans)
    if not seen:
        return None
    return 100.0 * sum(int(a["sparse_key_tokens"]) for a in spans) / seen
