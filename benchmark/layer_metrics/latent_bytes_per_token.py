"""What a cached token costs the latent pool over the layers, as the pool's
leaves are allocated: ``latent_bytes_per_token`` of the ``engine.dispatch``
spans (the program's own count from its cache's shapes: 10,240 for eight
layers of a 640-wide bf16 row; per-head keys and values would be 163,840).
Spans without the count (a model with per-head K/V, or the parent of the PR
that added it) give nothing to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    got = [int(e[3]["latent_bytes_per_token"])
           for e in ht.spans("engine.dispatch")
           if "latent_bytes_per_token" in e[3]]
    return sum(got) / len(got) if got else None
