"""vusa_fused_moe_matmul's share of its roofline: the least time of its calls in the traced
part of the window over their device time.  A call's least time is the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (work.py: the pack counted once per call, as if every slot's row
shared one stream of it).  The calls of one decode step repeat in a fixed
order, so the traced calls are whole steps of that list."""

KERNEL = "vusa_fused_moe_matmul"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    count, secs = ctx.trace.kernels.get(KERNEL, (0, 0.0))
    calls = ctx.calls[KERNEL]
    if not count or not secs:
        return None
    least = sum(c.least_s(ctx.peaks) for c in calls) * count / len(calls)
    return 100.0 * least / secs
