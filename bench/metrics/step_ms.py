"""step_ms (ms): the window's wall time over the train steps it completed.
Host clock; every step ends with its work complete on the device."""


def read(ctx):
    if ctx.shape.get("unit") != "step" or not ctx.window.units:
        return None
    return 1e3 * ctx.window.seconds / ctx.window.units
