"""GLM-5's indexer over prompt chunks (scores and the exact choice) as a
share of its roofline, which is compute: ``2 HI dI`` flops a (query, key)
pair the indexer scores for a prompt row (``index_prefill_tokens`` of the
fused ticks' ``engine.dispatch`` spans: ``t + 1`` a row;
``flops_glm5.index_scores``) / peak bf16 FLOP/s, over the device self time,
in the fused tick, of the module ``indexer``, the scopes ``index`` and
``select`` and the kernel ``index_select``, which scores and chooses in
one.  The choice is 32 passes of the VPU over every score and the relu, the
head weights and the sum over 32 heads are the VPU's too, none of which the
needed work counts: they hold the share down."""
from benchmark import flops_glm5
from benchmark.layer_metrics import glm5_trace as gt


def read(ctx):
    secs = gt.total(gt.seconds(ctx, gt.CHUNK_INDEX),
                    gt.seconds(ctx, (gt.INDEXER,), "fused"))
    if not secs or not gt.mine(ctx):
        return None
    cfg = ctx["cell"].config
    need = gt.needed(ctx, lambda program, d: gt.bound_s(
        ctx, flops_glm5.index_scores(
            cfg, int(d.get("index_prefill_tokens", 0)),
            gt.chunk_rows(program, d), shared_keys=True)))
    return 100.0 * need / secs if need else None
