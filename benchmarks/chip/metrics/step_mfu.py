"""Model FLOPs of the window's served work over the window's seconds times
the chip's bf16 peak.  Model FLOPs are the dense-equivalent ones the
family module counts (``ctx.prefill_flops``, ``ctx.decode_flops``): for a
dense LM 2 per matmul parameter for each prefilled prompt token (the head
once per prompt) and each decoded token, plus attention over each token's
context; a packed kernel gets no credit for the zeros it skips, and decode
steps of free slots count for nothing."""


def read(ctx):
    if ctx.peaks is None:
        return None
    flops = sum(ctx.prefill_flops(n) for t, n in ctx.prefills if t <= ctx.window_s)
    for r in ctx.records:
        # token 0 comes from the prefill; token j from a decode step at context prompt + j
        flops += sum(ctx.decode_flops(r["prompt"] + j) for j in range(1, r["n_in"]))
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flop_per_s"])
