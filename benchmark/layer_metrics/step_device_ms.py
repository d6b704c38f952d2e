"""Device-busy milliseconds per optimizer step in the traced steps, mean
over chips."""


def read(ctx):
    red, steps = ctx.get("trace"), ctx.get("trace_steps")
    return None if red is None or not steps else 1e3 * red.busy_s / steps
