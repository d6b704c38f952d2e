"""Share of the traced steps' time in which a chip sat in a collective
(all-gather, reduce-scatter, all-reduce) and ran nothing else: exposed
collective seconds / traced extent, mean over chips."""


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    return 100.0 * red.collective_exposed_s / red.window_s
