"""Padding share of the prefill work the run dispatched: 1 - real prompt
tokens / positions the prefill programs computed (bucket length x padded
batch rows), from ``Scheduler.stats()``'s ``prefill_tokens`` and
``prefill_positions`` counters.  None where the program keeps no such
counters or prefilled nothing."""


def read(ctx):
    pos = ctx.stats.get("prefill_positions")
    if not pos or "prefill_tokens" not in ctx.stats:
        return None
    return 100.0 * (1.0 - ctx.stats["prefill_tokens"] / pos)
