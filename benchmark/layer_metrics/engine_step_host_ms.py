"""The serving loop's whole host cost per compiled-program launch, from
inside: (sum of ``engine.step`` spans - sum of the ``engine.fetch`` spans in
them, the time the host was only blocked on the device) / ``engine.dispatch``
spans in them, over the steps that began and ended in the traced slice, in
milliseconds.  ``host_ms_per_dispatch`` sums four brackets from outside;
this one holds everything a ``step()`` does."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    dispatches = ht.spans("engine.dispatch", inside="engine.step")
    if not dispatches:
        return None
    host_ns = (sum(e[2] for e in ht.spans("engine.step")) -
               sum(e[2] for e in ht.spans("engine.fetch",
                                          inside="engine.step")))
    return host_ns / 1e6 / len(dispatches)
