"""The device's idle share of the traced slice: 1 - union of the device
operations' intervals / traced extent; over several chips the mean."""


def read(ctx):
    red = ctx.get("trace")
    return None if red is None else 100.0 * red.idle_share
