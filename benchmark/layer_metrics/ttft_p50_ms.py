"""Median time from due to the first streamed token at the client socket,
over the window's completed requests."""
from benchmark import harness as H


def read(ctx):
    ttft = [r["first_t"] - r["due_t"] for r in ctx.get("sample", [])
            if r["ok"]]
    return 1e3 * H.percentile(ttft, 50) if ttft else None
