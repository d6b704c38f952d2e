"""A hybrid model's decode block as a share of its roofline, which is
memory, from the program's own counts.  Bytes a block must move = ticks x
the fixed weights (conv and attention mixers, dense MLPs, routers, head) +
one expert's bank for each expert HIT (``moe_experts_hit`` on the block's
``engine.moe`` span, summed over layers and ticks) + the keys and values of
every live context token at the pool's stored width (``rows``, ``ticks``,
``key_tokens`` on the block's ``engine.dispatch`` span, as
``decode_roofline_counted``) + the convolution state of every live row;
that / HBM bytes per second, over the mean device time of the decode
program's runs that lie WHOLLY in the slice (``loop_decode_roofline.
whole_runs``: a chip's first and last run of the slice are the ones the
profiler may have cut).

``flops_hybrid.py`` counts; nothing is counted that a step does not need,
so the share cannot pass 100 %.  A program whose dispatch spans carry no
``state_layers`` (a model without per-slot state, or the parent of the PR
that added it) gives nothing to read."""
from benchmark import flops_hybrid
from benchmark.layer_metrics import host_spans, loop_decode_roofline, moe_trace


def blocks(ctx):
    """The counts of the decode blocks' ``engine.dispatch`` spans of the
    traced slice that say how many layers keep per-slot state, in order."""
    ht = host_spans.load(ctx)
    if ht is None:
        return []
    return [e[3] for e in sorted(ht.spans("engine.dispatch"),
                                 key=lambda e: e[1])
            if e[3].get("program") == "decode_block"
            and "state_layers" in e[3]]


def read(ctx):
    spans = blocks(ctx)
    moe = moe_trace.spans(ctx, "decode_block")
    if not spans or not moe:
        return None
    cfg = ctx["cell"].config
    runs = loop_decode_roofline.whole_runs(ctx, cfg["programs"]["decode"])
    if not runs:                # no whole block to time
        return None
    ticks = int(spans[0]["ticks"])
    hit = sum(int(m["moe_experts_hit"]) for m in moe) / len(moe) / ticks
    # a block's mean step: the first reads ``key_tokens`` context tokens in
    # all, every later one a token more a row
    need = sum(ticks * flops_hybrid.decode_step_bytes(
        cfg, hit, int(b["key_tokens"]) + int(b["rows"]) * (ticks - 1) / 2.0,
        int(b["rows"])) for b in spans) / len(spans)
    return (100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"])
            / (sum(runs) / len(runs)))
