"""What a cached token's index keys cost the pool over the layers, as the
pool's leaves are allocated: ``index_bytes_per_token`` of the
``engine.dispatch`` spans (the program's own count from its cache's shapes:
six layers of one 64-wide bf16 key, 768; its keys and values beside them
are 12,288).  Spans without the count (a model without an indexer, or the
parent of the PR that added it) give nothing to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    got = [int(e[3]["index_bytes_per_token"])
           for e in ht.spans("engine.dispatch")
           if "index_bytes_per_token" in e[3]]
    return sum(got) / len(got) if got else None
