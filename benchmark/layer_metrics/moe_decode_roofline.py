"""An expert model's decode block as a share of its roofline, which is
memory, from the program's own counts.  Bytes a block must move = ticks x
the dense weights (attention, router, head) + 12.6 MB for each expert HIT
(``moe_experts_hit`` on the block's ``engine.moe`` span, summed over layers
and ticks: an expert no row took is not read) + 64 KiB a live context token
(``rows``, ``ticks``, ``key_tokens`` on the block's ``engine.dispatch``
span, as ``decode_roofline_counted``); that / HBM bytes per second, over the
device time of a block.

Both sides per block: mean needed bytes of the blocks whose spans lie in the
slice over mean device time of the blocks RUN in it.  ``flops_moe.py``
counts; nothing is counted that the step did not need, so the share cannot
pass 100 %."""
from benchmark import flops_moe
from benchmark.layer_metrics import host_spans, moe_trace
from benchmark.layer_metrics import windows as W


def read(ctx):
    ht = host_spans.load(ctx)
    secs = W.program_seconds(ctx, "decode")
    moe = moe_trace.spans(ctx, "decode_block")
    if ht is None or not secs or not moe:
        return None
    blocks = [e[3] for e in ht.spans("engine.dispatch")
              if e[3].get("program") == "decode_block"]
    if not blocks:
        return None
    cfg = ctx["cell"].config
    ticks = int(blocks[0]["ticks"])
    context = sum(int(b["ticks"]) * (int(b["key_tokens"]) + int(b["rows"])
                                     * (int(b["ticks"]) - 1) / 2.0)
                  for b in blocks) / len(blocks)
    hit = sum(int(m["moe_experts_hit"]) for m in moe) / len(moe)
    need = ticks * flops_moe.decode_step_bytes(cfg, hit / ticks,
                                               context / ticks)
    per_run = secs / ctx["trace"].program_runs[cfg["programs"]["decode"]]
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / per_run
