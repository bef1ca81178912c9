"""Host milliseconds the serving loop spends per segment sync outside the
token fetch and the caller's hook: the ``serve.admit``, ``serve.dispatch``
and ``serve.consume`` spans of ``Scheduler.run`` (their seconds in
``Scheduler.stats()``, program counters) over the run's syncs.  None where
the program keeps no such spans."""

KEYS = ("admit_s", "dispatch_s", "consume_s")


def read(ctx):
    st = ctx.stats
    if not st.get("syncs") or any(k not in st for k in KEYS):
        return None
    return 1000.0 * sum(st[k] for k in KEYS) / st["syncs"]
