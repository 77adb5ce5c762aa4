"""mfu.train_hybrid (%): model FLOPs of the hybrid Mamba2 / MoE / attention
train steps' forward and backward over their measured time (the traced
window) times the chips times their peak bf16 FLOP/s. Recomputation and
the expert dispatch's padding rows do not count.

Forward FLOPs, from the configuration's shapes, 2 per multiply-add: per
token the projections of each M block (in_proj, out_proj) and its grouped
chunked SSD (C·Bᵀ scores per group and scores·x per head inside a chunk,
chunk states in and out), of each * block (q, k, v, o) and its causal
scores (q·kᵀ and weights·v over the (S + 1)/2 positions a query sees on
average), of each E block the router and the shared expert, and the head;
and per slot the step routed to a held expert (``routed_slots``, counted by
the program), that expert's two matrices. Backward is twice the forward."""


def dense_forward_flops_per_token(c: dict, seq: int) -> float:
    d = int(c["hidden_size"])
    h, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    g, n = int(c["n_groups"]), int(c["ssm_state_size"])
    q = int(c["chunk_size"])
    di = h * p
    hq, hkv, dh = (int(c["num_attention_heads"]),
                   int(c["num_key_value_heads"]), int(c["head_dim"]))
    fs = (int(c["moe_shared_expert_intermediate_size"])
          * int(c["n_shared_experts"]))
    per = {
        "M": (2 * d * (2 * di + 2 * g * n + h) + 2 * di * d
              + 2 * q * g * n + 2 * q * h * p + 2 * 2 * h * p * n),
        "*": (2 * d * (hq + 2 * hkv) * dh + 2 * hq * dh * d
              + 2 * 2 * hq * dh * (seq + 1) / 2),
        "E": 2 * d * int(c["n_routed_experts"]) + 2 * 2 * d * fs,
    }
    vocab = int(c["assumed"]["padded_vocab"])
    return float(sum(per[k] for k in c["hybrid_override_pattern"])
                 + 2 * vocab * d)


def routed_flops_per_slot(c: dict) -> float:
    return 2.0 * 2 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def read(ctx):
    t, s = ctx.trace, ctx.shape
    if (t is None or s.get("unit") != "step" or "routed_slots" not in s
            or not ctx.window.trace_units):
        return None
    slots = s["routed_slots"][:ctx.window.trace_units]
    fwd = (len(slots) * dense_forward_flops_per_token(s["config"], s["seq"])
           * s["tokens"] + routed_flops_per_slot(s["config"]) * sum(slots))
    return 100.0 * 3.0 * fwd / (t.window_s * t.chips
                                * ctx.peaks["bf16_flops_per_s"])
