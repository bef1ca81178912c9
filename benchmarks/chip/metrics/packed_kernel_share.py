"""Device time of both packed kernels over the device time of the decode
segment programs that run them, in the traced part of the window."""


def read(ctx):
    if ctx.trace is None:
        return None
    _, seg = ctx.trace.module_time("segment")
    k = sum(secs for _, secs in ctx.trace.kernels.values())
    return 100.0 * k / seg if seg and k else None
