"""Rows each held expert computed for the tokens routed to it, per decode
step and expert layer: ``Scheduler.stats()``'s ``expert_rows`` (the (row,
held expert) pairs routing chose over the slots holding a request) over
``decode_steps`` x ``expert_layers`` x ``held_experts``, program counters.
With the pool full it reads slots x experts per token / experts.  None
where the program keeps no such counters or holds no experts."""


def read(ctx):
    st = ctx.stats
    n = st.get("decode_steps", 0) * st.get("expert_layers", 0) * st.get("held_experts", 0)
    if not n or "expert_rows" not in st:
        return None
    return st["expert_rows"] / n
