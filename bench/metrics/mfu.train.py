"""mfu.train (%): model FLOPs of a train step's forward and backward over
the step's measured time (traced window over its steps) times the chips
times their peak bf16 FLOP/s. Recomputation does not count.

Forward FLOPs of one token, from the configuration's shapes: 2 per
multiply-add of every projection (in_proj, out_proj) and of the tied head,
plus the chunked SSD products (C·Bᵀ scores and scores·x inside a chunk,
chunk states in and out); backward is twice the forward."""


def forward_flops_per_token(c: dict) -> float:
    d = int(c["d_model"])
    di = int(c["expand"]) * d
    n = int(c["d_state"])
    p = int(c["headdim"])
    h = di // p
    q = int(c["chunk_size"])
    vocab = int(c["assumed"]["padded_vocab"])
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    ssd = 2 * q * n + 2 * q * h * p + 2 * 2 * h * p * n
    return float(int(c["n_layer"]) * (proj + ssd) + 2 * vocab * d)


def read(ctx):
    t, s = ctx.trace, ctx.shape
    if t is None or s.get("unit") != "step" or not ctx.window.trace_units:
        return None
    flops = 3.0 * forward_flops_per_token(s["config"]) * s["tokens"]
    per_step = t.window_s / ctx.window.trace_units
    return 100.0 * flops / (per_step * t.chips
                            * ctx.peaks["bf16_flops_per_s"])
