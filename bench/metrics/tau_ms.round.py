"""tau_ms.round (ms): device time per round of the τ search
(``core/sparsify.py``: the count_ge, count_ge_fused and hist_topq kernels,
and sort and top-k operations)."""

TAU = r"(count_ge|count_ge_fused|hist_topq)_level_pallas|sort|top-?k"


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "round" \
            or not ctx.window.trace_units:
        return None
    return 1e3 * t.op_seconds(TAU) / ctx.window.trace_units
