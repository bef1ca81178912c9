"""Device time of one decode step of the whole slot pool: the decode
segment programs in the trace, over the steps they ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    count, secs = ctx.trace.module_time("segment")
    if not count:
        return None
    return 1000.0 * secs / (count * ctx.serve["segment"])
