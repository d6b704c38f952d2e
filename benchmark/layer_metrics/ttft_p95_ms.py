"""Time from DUE to the first streamed token at the client socket, 95th
percentile over the requests due in the window; a failed or unfinished
request counts as the worst (the end of the drain).  What a chat user
waits for before anything shows.  Recorded, not bounded: between runs of
one tree it spreads by 8 % and more (PERF.md, Findings, PR 24)."""
from benchmark import harness as H


def read(ctx):
    sample = ctx.get("sample", [])
    if not sample:
        return None
    worst = max(r["end_t"] for r in sample)
    return 1e3 * H.percentile(
        [(r["first_t"] if r["ok"] else worst) - r["due_t"] for r in sample],
        95)
