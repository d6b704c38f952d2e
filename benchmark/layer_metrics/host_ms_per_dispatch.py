"""Host milliseconds per compiled-program launch in the window:
(plan + upload + dispatch + harvest seconds) / dispatches, the window's
delta of ``serving_stages()``."""


def read(ctx):
    a, b = ctx.get("stages_before"), ctx.get("stages_after")
    if not a or not b or b["dispatches"] <= a["dispatches"]:
        return None
    host = sum(b[s] - a[s] for s in ("plan", "upload", "dispatch", "harvest"))
    return 1e3 * host / (b["dispatches"] - a["dispatches"])
