"""The indexer over one-row sequences (a decode block's rows, a fused
tick's decoding sequences) as a share of its roofline, which is memory: a
row reads the index keys of every token it sees, ``(t + 1) * 128`` bytes a
layer (``index_decode_tokens`` of the ``engine.dispatch`` spans of both
programs; ``flops_keye.index_scores``) / HBM bytes per second, over the
device self time of the scopes ``index_row`` and ``select_row`` (and, in a
decode block, of the module ``indexer``).  The kernel ``row_index_scores``
reads the pages a row sees; the choice sorts every score (``lax.top_k``)
and reads and writes them as float32 besides, which is what holds the
share down."""
from benchmark import flops_keye
from benchmark.layer_metrics import keye_trace as kt


def read(ctx):
    secs = kt.total(kt.seconds(ctx, kt.ROW_INDEX),
                    kt.seconds(ctx, (kt.INDEXER,), "decode_block"))
    if not secs:
        return None
    cfg = ctx["cell"].config
    need = kt.needed(ctx, lambda program, d: kt.bound_s(
        ctx, flops_keye.index_scores(
            cfg, int(d.get("index_decode_tokens", 0)),
            kt.one_row_steps(program, d), shared_keys=False)))
    return 100.0 * need / secs if need else None
