"""What a sequence's per-slot state costs beside its pages, as the cache's
state leaves are allocated: ``state_bytes`` of the ``engine.dispatch`` spans
(the program's own count from its cache's shapes, whatever the leaves hold:
lightning attention's nine layers of 32 heads of a 128 x 128 float32 matrix,
18,874,368; the gated delta rule's nine layers of 30 heads of a 96 x 192
float32 matrix beside the short convolution's three carried rows of q~, k~
and v~ in bf16, 9 x (2,211,840 + 69,120) = 20,528,640; three layers of 64
heads of a 128 x 128 float32 matrix under a vector decay beside their carried
rows, 3 x (4,194,304 + 147,456) = 13,025,280).  Spans without the
count (a model without per-slot state, or the parent of the PR that added
it) give nothing to read."""
from benchmark.layer_metrics import host_spans


def read(ctx):
    ht = host_spans.load(ctx)
    if ht is None:
        return None
    got = [int(e[3]["state_bytes"]) for e in ht.spans("engine.dispatch")
           if "state_bytes" in e[3]]
    return sum(got) / len(got) if got else None
