"""sim_round_ms (ms): the window's wall time over the simulator rounds it
completed, a round being local gradients plus one aggregation, dispatched
as ``Simulator.run`` dispatches them. Host clock; every timed unit ends
with its rounds' logs fetched from the device."""


def read(ctx):
    if ctx.shape.get("unit") != "sim_round" or not ctx.window.units:
        return None
    return 1e3 * ctx.window.seconds / ctx.window.units
