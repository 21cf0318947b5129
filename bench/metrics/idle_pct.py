"""Device idle share of the traced window: 1 - union of the device's
operation intervals over the window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
