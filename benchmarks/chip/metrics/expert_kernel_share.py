"""Device time of the grouped expert kernel (``vusa_fused_moe_matmul``, an
expert layer's held experts in one call) over the device time of the decode
segment programs that run it, in the traced part of the window.  None where
the trace holds no such kernel."""

KERNEL = "vusa_fused_moe_matmul"


def read(ctx):
    if ctx.trace is None:
        return None
    _, seg = ctx.trace.module_time("segment")
    _, secs = ctx.trace.kernels.get(KERNEL, (0, 0.0))
    return 100.0 * secs / seg if seg and secs else None
