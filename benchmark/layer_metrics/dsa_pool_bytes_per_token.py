"""What a cached token costs GLM-5's two pool leaves over the layers, as the
leaves are allocated: ``latent_bytes_per_token + index_bytes_per_token`` of
the ``engine.dispatch`` spans (the program's own count from its cache's
shapes: six layers of a 640-wide bf16 row and a 128-wide bf16 index key,
7,680 + 1,536 = 9,216; per-head keys and values of 64 heads would be
393,216).  Spans that lack either count (every other model, or the parent of
the PR that added the pair) give nothing to read."""
from benchmark.layer_metrics import host_spans

KEYS = ("latent_bytes_per_token", "index_bytes_per_token")


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    got = [sum(int(e[3][k]) for k in KEYS)
           for e in ht.spans("engine.dispatch")
           if all(k in e[3] for k in KEYS)]
    return sum(got) / len(got) if got else None
