"""node_step_roofline (%): the node step's least HBM traffic over the device
time of the node-step kernels (``kernels/level.py``: sparsify_ef,
chain_accum, cl_fuse), summed over a traced window's rounds.

Least bytes per hop: read g_k, e_k and γ_in, write e'_k and γ_out, 5 words
per entry, whatever kernels implement it."""

KERNELS = r"(sparsify_ef|chain_accum|cl_fuse)_level_pallas"


def read(ctx):
    t, s = ctx.trace, ctx.shape
    if t is None or s.get("unit") != "round" or not ctx.window.trace_units:
        return None
    secs = t.op_seconds(KERNELS)
    if secs <= 0:
        return None
    nbytes = 5.0 * s["entry_bytes"] * s["hops"] * s["d"] \
        * ctx.window.trace_units
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
