"""A looped model's decode block as a share of its roofline, which is
memory, from the program's own counts.  Bytes a block must move = ticks x
(``total_ut_steps`` x every block's matrices + the head) + the keys and
values of every (pass, layer) slot of each live context token (``rows``,
``ticks``, ``key_tokens`` on the block's ``engine.dispatch`` span, as
``decode_roofline_counted``: the first tick reads ``key_tokens`` context
tokens in all and every later one a token more a row); that / HBM bytes per
second, over the device time of a block.

Both sides per block: mean needed bytes of the blocks whose spans lie in the
slice over the mean device time of the decode program's runs that lie WHOLLY
in it.  Not device seconds / runs: a block of this model is a quarter of a
second, so the slice holds ten of them, and the profiler records the two it
cuts at its ends with the part it saw (24 ms and 248 ms beside 271-282 ms;
my chip run, PR 32).  Seconds / runs then reads a block a tenth short and
the share a tenth high, over 100 where the slice happens to cut eleven.  So
a chip's first and last program run of the slice are left out, and with
them the first and the last of the blocks' dispatch spans, so that bytes
and time are of the same blocks (the host dispatches a block before the
device runs it, so the pairing is by order, not by instant).
``flops_loop.py`` counts; nothing is counted that a step does not need, so
the share cannot pass 100 %.  A program whose dispatch spans carry no
``ut_steps`` (a model whose layers run once, or the parent of the PR that
added the count) gives nothing to read."""
import glob
import os

from benchmark import flops_loop
from benchmark import harness as H
from benchmark import trace_reduce as TR
from benchmark.layer_metrics import host_spans


def blocks(ctx):
    """The counts of the decode blocks' ``engine.dispatch`` spans of the
    traced slice that say how many passes a token takes, in order."""
    ht = host_spans.load(ctx)
    if ht is None:
        return []
    return [e[3] for e in sorted(ht.spans("engine.dispatch"),
                                 key=lambda e: e[1])
            if e[3].get("program") == "decode_block" and "ut_steps" in e[3]]


def whole_runs(ctx, program):
    """Device seconds of each run of ``program`` that lies wholly inside
    THIS run's traced slice: a chip's first and last run of any program
    are the ones the slice may have cut (kept on ``ctx``)."""
    if "_module_runs" not in ctx:
        # ``host_spans.load`` has found and checked the run's trace
        # directory (the newest of the cell, its extent the driver's)
        if host_spans.load(ctx) is None:
            return []
        dirs = glob.glob(os.path.join(
            H.OUT_DIR, f"trace-{ctx['cell'].name}-*"))
        planes = TR.load_xplane(TR.newest_xplane(
            max(dirs, key=os.path.getmtime)))
        ctx["_module_runs"] = [
            sorted((s, d / 1e9, TR.program_name(n))
                   for n, s, d in lines.get(TR.MODULES_LINE, []))
            for lines in planes.values()]
    return [d for chip in ctx["_module_runs"] for _, d, name in chip[1:-1]
            if name == program]


def read(ctx):
    spans = blocks(ctx)
    if not spans:
        return None
    cfg = ctx["cell"].config
    runs = whole_runs(ctx, cfg["programs"]["decode"])
    if not runs:                # no whole block to time
        return None
    if len(spans) > 2:          # the blocks the slice cut, as on the device
        spans = spans[1:-1]
    need = sum(flops_loop.decode_step_bytes(cfg, 0) * int(b["ticks"])
               + flops_loop.kv_bytes_per_token(cfg) * int(b["ticks"])
               * (int(b["key_tokens"]) + int(b["rows"])
                  * (int(b["ticks"]) - 1) / 2.0)
               for b in spans) / len(spans)
    return (100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"])
            / (sum(runs) / len(runs)))
