"""Device idle time, as a percentage of the traced extent, while the replica's
worker thread was in ``engine.sample``, ``engine.fetch``, ``engine.harvest``
or ``engine.reap``: eager sampling, the blocking fetch, folding tokens into
request state.  One of five shares that partition the idle time
(``host_spans.idle_by_class``): together they are ``device_idle_pct``."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    return host_spans.idle_pct(ctx, "sample")
