"""The indexer over prompt chunks (scores and the exact choice) as a share
of its roofline, which is compute: ``2 HI dI`` flops a (query, key) pair
the indexer scores for a prompt row (``index_prefill_tokens`` of the fused
ticks' ``engine.dispatch`` spans: ``t + 1`` a row; ``flops_keye.
index_scores``) / peak bf16 FLOP/s, over the device self time, in the fused
tick, of the module ``indexer``, the scopes ``index`` and ``select`` and the
kernel ``index_select``, which scores and chooses in one (``index_scores``,
the scoring kernel its parent had beside it, is still summed where a trace
holds it).  The products contract over 64 (half of the MXU's width) and the
choice is 32 passes of the VPU over every score, neither of which the
needed work counts: both hold the share down."""
from benchmark import flops_keye
from benchmark.layer_metrics import keye_trace as kt


def read(ctx):
    secs = kt.total(kt.seconds(ctx, kt.CHUNK_INDEX),
                    kt.seconds(ctx, (kt.INDEXER,), "fused"))
    if not secs or not kt.seconds(ctx, kt.OWN):
        return None
    cfg = ctx["cell"].config
    need = kt.needed(ctx, lambda program, d: kt.bound_s(
        ctx, flops_keye.index_scores(
            cfg, int(d.get("index_prefill_tokens", 0)),
            kt.chunk_rows(program, d), shared_keys=True)))
    return 100.0 * need / secs if need else None
