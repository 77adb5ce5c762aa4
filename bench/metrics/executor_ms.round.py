"""executor_ms.round (ms): device time per round of the executor
(``agg/plan.py::execute``): every XLA operation that is neither a node-step
or τ-search kernel, nor a sort or top-k, nor a collective. Row gathers,
scatter-adds, relayout copies and the τ search's bracket-top reduction
fall here."""

NOT_EXECUTOR = (r"(sparsify_ef|chain_accum|cl_fuse|count_ge|count_ge_fused"
                r"|hist_topq)_level_pallas|sort|top-?k|collective-permute"
                r"|all-gather|all-reduce|reduce-scatter|all-to-all")


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "round" \
            or not ctx.window.trace_units:
        return None
    rest = t.total_op_seconds() - t.op_seconds(NOT_EXECUTOR)
    return 1e3 * rest / ctx.window.trace_units
