"""The most the KV pool held in the traced slice, as a share of its usable
pages: the largest ``pages_in_use`` / ``pages_total`` over the
``engine.dispatch`` spans (the allocator's own numbers at each dispatch:
whole pages granted, whatever a page holds).  The window line's
``kv_pool_filled`` estimates the same over the whole window from request
records, against the same count of usable pages.  A program whose spans
carry no page counts gives nothing to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    shares = [int(a["pages_in_use"]) / int(a["pages_total"])
              for a in (e[3] for e in ht.spans("engine.dispatch"))
              if "pages_in_use" in a and int(a.get("pages_total", 0)) > 0]
    return 100.0 * max(shares) if shares else None
