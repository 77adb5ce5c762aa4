"""expert_rows_wasted.train_hybrid (%): the share of the rows that the
expert layers' matmuls ran over the traced steps that no slot routed to
them filled: 100·(expert_rows − routed_slots)/expert_rows, both counted by
the program (``expert_rows``: every (token, held expert) row it computed)."""


def read(ctx):
    s = ctx.shape
    if (ctx.trace is None or s.get("unit") != "step"
            or "expert_rows" not in s or not ctx.window.trace_units):
        return None
    n = ctx.window.trace_units
    rows, slots = sum(s["expert_rows"][:n]), sum(s["routed_slots"][:n])
    return 100.0 * (rows - slots) / rows if rows else None
