"""Share of slot-steps the Scheduler decoded for a live request over the
whole run (``Scheduler.stats()['slot_occupancy']``, a program counter)."""


def read(ctx):
    return 100.0 * ctx.stats["slot_occupancy"]
