"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (window), averaged over the
chips in use."""


def read(ctx):
    w = ctx.trace.window_s
    if w <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / w)
