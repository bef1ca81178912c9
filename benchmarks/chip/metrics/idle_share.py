"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips used."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.window_s or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
