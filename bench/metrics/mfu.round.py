"""mfu.round (%): the least time of an aggregation round's required work
over the measured time of a round (traced window over the rounds in it).

Least time = max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s), from
shapes alone, whatever implements the round: per hop, read g_k, e_k and
γ_in, write e'_k and γ_out: 5 words per entry (20 B in f32), about 4
FLOPs per entry.
"""


def least_work(s: dict) -> tuple:
    """→ (flops, bytes) of one round."""
    hop_entries = s["hops"] * s["d"]
    return 4.0 * hop_entries, 5.0 * s["entry_bytes"] * hop_entries


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "round" \
            or not ctx.window.trace_units:
        return None
    flops, nbytes = least_work(ctx.shape)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    per_round = t.window_s / ctx.window.trace_units
    return 100.0 * least / per_round
