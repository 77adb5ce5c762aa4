"""idle_share.round (%): share of the traced window of aggregation-round cells in which
no operation ran on the chip (1 - busy / window, mean over chips)."""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "round" or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
