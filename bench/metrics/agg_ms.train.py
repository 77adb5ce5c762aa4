"""agg_ms.train (ms): device time per train step of the aggregation phase
of ``train/step.py``: the Pallas node-step kernels and the sort and top-k
operations of the exact Top-Q."""

AGG = (r"(sparsify_ef|chain_accum|cl_fuse|count_ge|count_ge_fused|hist_topq)"
       r"_level_pallas|sort|top-?k")


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "step" \
            or not ctx.window.trace_units:
        return None
    return 1e3 * t.op_seconds(AGG) / ctx.window.trace_units
