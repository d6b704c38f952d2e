"""Per-slot state rows a tick reads and writes: ``state_rows`` (sequences
whose state the program touches) x ``state_layers`` of the
``engine.dispatch`` spans of the traced slice, mean over the spans: 8 x the
live rows in ``lfm2-reasoning-closed``.  The proof that the state path ran;
spans without the two counts (a model without per-slot state) give nothing
to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    rows = [int(a["state_rows"]) * int(a["state_layers"])
            for a in (e[3] for e in ht.spans("engine.dispatch"))
            if "state_rows" in a and "state_layers" in a]
    return sum(rows) / len(rows) if rows else None
