"""The decode block's share of its roofline, which is memory, from the
program's own counts: each ``engine.dispatch`` span of a decode block
carries its live ``rows``, its ``ticks`` (token steps) and ``key_tokens``,
the context tokens its first step reads as the host projects them at
dispatch; every later step reads one token more per row.  Bytes a block
must move = ticks x (weights + KV bytes x (key_tokens + rows x (ticks - 1)
/ 2)); that / HBM bytes per second, over the device time of a block.

Both sides per block, as in ``prefill_roofline_counted``: mean needed
bytes of the blocks DISPATCHED in the slice over mean device time of the
blocks RUN in it.  (The estimate of the live contexts from the requests'
records that stood beside it, ``decode_roofline``, went with PR 42: the
two read within 0.1 point in the ledger.)"""
from benchmark import flops
from benchmark.layer_metrics import host_spans
from benchmark.layer_metrics import windows as W


def read(ctx):
    ht = host_spans.load(ctx)
    secs = W.program_seconds(ctx, "decode")
    if ht is None or not secs:
        return None
    blocks = [e[3] for e in ht.spans("engine.dispatch")
              if e[3].get("program") == "decode_block"]
    if not blocks:
        return None
    cfg = ctx["cell"].config
    need = sum(int(b["ticks"]) * flops.decode_step_bytes(
        cfg, int(b["key_tokens"]) +
        int(b["rows"]) * (int(b["ticks"]) - 1) / 2.0)
        for b in blocks) / len(blocks)
    per_run = secs / ctx["trace"].program_runs[cfg["programs"]["decode"]]
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / per_run
