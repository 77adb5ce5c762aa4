"""round_ms (ms): the window's wall time over the aggregation rounds it
completed, a round being one ``execute`` call. Host clock; every round
ends with its work complete on the device."""


def read(ctx):
    if ctx.shape.get("unit") != "round" or not ctx.window.units:
        return None
    return 1e3 * ctx.window.seconds / ctx.window.units
