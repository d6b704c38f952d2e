"""GLM-5's indexer over one-row sequences (a decode block's rows, a fused
tick's decoding sequences) as a share of its roofline, which is memory: a
row reads the index key of every token it sees, ``(t + 1) * 256`` bytes a
layer (``index_decode_tokens`` of the ``engine.dispatch`` spans of both
programs; ``flops_glm5.index_scores``) / HBM bytes per second, over the
device self time of the scopes ``index_row`` and ``select_row`` (and, in a
decode block, of the module ``indexer``).  The kernel ``row_index_scores``
reads the pages a row sees; the choice sorts every score (``lax.top_k``)
and reads and writes them as float32 besides, which holds the share
down."""
from benchmark import flops_glm5
from benchmark.layer_metrics import glm5_trace as gt


def read(ctx):
    secs = gt.total(gt.seconds(ctx, gt.ROW_INDEX),
                    gt.seconds(ctx, (gt.INDEXER,), "decode_block"))
    if not secs or not gt.mine(ctx):
        return None
    cfg = ctx["cell"].config
    need = gt.needed(ctx, lambda program, d: gt.bound_s(
        ctx, flops_glm5.index_scores(
            cfg, int(d.get("index_decode_tokens", 0)),
            gt.one_row_steps(program, d), shared_keys=False)))
    return 100.0 * need / secs if need else None
