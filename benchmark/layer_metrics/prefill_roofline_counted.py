"""The SplitFuse tick's share of its roofline, which is compute, from the
program's own counts: each ``engine.dispatch`` span of a fused tick carries
the prompt tokens it packed (``prefill_tokens``) and the keys they attend to
(``prefill_key_tokens``); the FLOPs they need / peak bf16 FLOP/s, over the
device time of the fused tick.

A tick dispatched in the slice may run after it and the slice's first run
was dispatched before it, so both sides are taken per tick: mean needed
work of the ticks DISPATCHED in the slice over mean device time of the
ticks RUN in it.  The decode rows that ride in the same ticks, and the
head, are left out of the needed work.  (The estimate from request
records that stood beside it, ``prefill_roofline``, went with PR 42: it
read within 2 points of this in every line of the ledger.)"""
from benchmark import flops
from benchmark.layer_metrics import host_spans
from benchmark.layer_metrics import windows as W


def read(ctx):
    ht = host_spans.load(ctx)
    secs = W.program_seconds(ctx, "prefill")
    if ht is None or not secs:
        return None
    ticks = [e[3] for e in ht.spans("engine.dispatch")
             if e[3].get("program") == "fused"]
    if not ticks:
        return None
    cfg = ctx["cell"].config
    need = sum(flops.prefill_flops(
        cfg, int(t["prefill_tokens"]),
        int(t["prefill_key_tokens"]) / int(t["prefill_tokens"]))
        for t in ticks if int(t["prefill_tokens"]) > 0) / len(ticks)
    per_run = secs / ctx["trace"].program_runs[cfg["programs"]["prefill"]]
    return 100.0 * (need / ctx["peaks"]["bf16_flops_per_s"]) / per_run
