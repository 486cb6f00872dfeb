"""Share of the traced window in which no op ran on the device: 100 times
1 minus the union of the device's op intervals over the window."""

UNIT = "%"


def read(ctx):
    w = ctx.trace.window_s()
    if w <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / w)
