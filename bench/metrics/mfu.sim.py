"""mfu.sim (%): the least time of a simulator round's required work over
the measured time of a round (traced window over the rounds in it).

Least time = max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s), from
shapes alone, whatever implements the round:

* aggregation, per hop: read g_k, e_k and γ_in, write e'_k and γ_out:
  5 words per entry (20 B in f32), about 4 FLOPs per entry;
* local gradients: one forward and backward of the linear model on each
  client's minibatch;
* the loss the round logs, over every training sample.
"""


def least_work(s: dict) -> tuple:
    """→ (flops, bytes) of one round."""
    hop_entries = s["hops"] * s["d"]
    dim, cls = s["input_dim"], s["classes"]
    per_sample = 2.0 * dim * cls
    flops = (4.0 * hop_entries + s["clients"] * s["batch"] * 2 * per_sample
             + s["samples"] * per_sample)
    nbytes = (5.0 * s["entry_bytes"] * hop_entries
              + 4.0 * dim * (s["clients"] * s["batch"] + s["samples"]))
    return flops, nbytes


def read(ctx):
    t = ctx.trace
    if t is None or ctx.shape.get("unit") != "sim_round" \
            or not ctx.window.trace_units:
        return None
    flops, nbytes = least_work(ctx.shape)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    per_round = t.window_s / ctx.window.trace_units
    return 100.0 * least / per_round
