"""The most the window layers' OWN pool held in the traced slice, as a share
of its usable pages: the largest ``window_pages_held`` / ``window_pages_total``
over the ``engine.dispatch`` spans (that pool's allocator's own numbers at
each dispatch).  ``kv_pool_peak_pct`` reads the same of the first pool, the
full layers'.  A program whose spans carry no such counts (every model whose
layers share one pool) gives nothing to read."""
from benchmark.layer_metrics import trinity_trace


def read(ctx):
    shares = [int(a["window_pages_held"]) / int(a["window_pages_total"])
              for a in trinity_trace.pool_spans(ctx)
              if int(a.get("window_pages_total", 0)) > 0]
    return 100.0 * max(shares) if shares else None
