"""Mixture-of-Experts FFN: grouped GShard-style einsum dispatch.

Two dispatch implementations were measured (EXPERIMENTS §Perf, pair B):

* scatter (``.at[e, pos].add``): memory-optimal single-device but opaque
  to GSPMD — the data-dependent scatter forces replication + 5.6 TB/step
  of gathers on mixtral prefill_32k;
* grouped one-hot einsum (this implementation, the GShard formulation):
  tokens are split into groups of ``group_size``; each group routes into
  per-group capacity buffers via one-hot einsums whose batch dims GSPMD
  shards cleanly. Dispatch-tensor memory is
  O(T × E × capacity/group) — bounded by the group size, not by T².

Token-dropping capacity semantics per group; active FLOPs ∝ top_k
(MODEL_FLOPS uses 6·N_active·D). Aux load-balancing loss = E·Σ f_e·p_e.

``moe_dropless`` (Nemotron-H) is the other layer: a sigmoid router over
every expert and a dropless share of them on this chip, dispatched as
static per-expert blocks or sorted into ragged matmuls
(``held_experts_ffn``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

GROUP_SIZE = 1024


def moe_capacity(group: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    cap = int(group * top_k * capacity_factor / num_experts)
    return max(4, (cap + 3) // 4 * 4)


def moe_ffn(params, x: Array, *, num_experts: int, top_k: int,
            capacity_factor: float, group_size: int = GROUP_SIZE):
    """x: [B, S, D] → (y [B, S, D], aux_loss scalar).

    params: router [D, E]; w_gate, w_up [E, D, F]; w_down [E, F, D].
    """
    b, s, d = x.shape
    t = b * s
    e = num_experts
    g = min(group_size, t)
    ng = t // g
    assert t % g == 0, (t, g)
    xg = x.reshape(ng, g, d)

    logits = jnp.einsum("ngd,de->nge", xg,
                        params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)              # [ng, g, k]
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch/Mixtral), over the pre-drop assignment
    assign = jax.nn.one_hot(topi, e, dtype=jnp.float32)   # [ng, g, k, E]
    frac_tokens = jnp.mean(assign.sum(2), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)

    cap = moe_capacity(g, e, top_k, capacity_factor)

    # position of each assignment inside its (group, expert) buffer:
    # priority = slot order (k-major within token, tokens in order)
    flat_assign = assign.reshape(ng, g * top_k, e)        # [ng, gk, E]
    pos = jnp.cumsum(flat_assign, axis=1) - flat_assign   # exclusive prefix
    pos = jnp.sum(pos * flat_assign, axis=-1)             # [ng, gk]
    keep = (pos < cap) & (jnp.sum(flat_assign, -1) > 0)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap,
                            dtype=xg.dtype)               # [ng, gk, cap]
    disp = (flat_assign.astype(xg.dtype)[..., None]
            * pos_oh[..., None, :])                       # [ng, gk, E, cap]

    # dispatch: [ng, gk, E, cap] × [ng, g(k-broadcast), D] → [ng, E, cap, D]
    x_rep = jnp.repeat(xg, top_k, axis=1)                 # [ng, gk, D]
    buf = jnp.einsum("ntec,ntd->necd", disp, x_rep)

    # expert SwiGLU over [E, ng·cap, D]
    hin = jnp.moveaxis(buf, 1, 0).reshape(e, ng * cap, d)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hin, params["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", hin, params["w_up"])
    hout = jnp.einsum("ecf,efd->ecd", gate * up, params["w_down"])
    hout = jnp.moveaxis(hout.reshape(e, ng, cap, d), 0, 1)  # [ng, E, cap, D]

    # combine with router weights on kept slots
    w = (topv.reshape(ng, g * top_k) * keep).astype(hout.dtype)
    y = jnp.einsum("ntec,nt,necd->ntd", disp, w, hout)    # [ng, gk, D]
    y = y.reshape(ng, g, top_k, d).sum(axis=2)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Dropless expert layer over a share of the experts (expert parallelism)
# ---------------------------------------------------------------------------

def route(x: Array, router_w: Array, *, top_k: int, routed_scale: float):
    """x [T, D] → (weights [T, k] f32, experts [T, k] int32) over every
    expert: the top-k sigmoid router scores, normalised over the chosen k
    and scaled by ``routed_scale``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    top, experts = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True) * routed_scale
    return weights, experts


def _relu2(h: Array) -> Array:
    return jnp.square(jax.nn.relu(h))


#: entries of the blocks dispatch's [held, T, F] float32 intermediate (1 GiB)
#: above which ``held_experts_ffn`` sorts the slots instead
BLOCKS_MAX_ENTRIES = 1 << 28


def held_experts_ffn(params, x: Array, weights: Array, experts: Array, *,
                     first: int, held: int):
    """The part of a dropless MoE output that experts [first, first + held)
    give: every slot routed to one of them is computed, the rest are left
    to the chips that hold them. x [T, D]; weights, experts [T, k]; params
    w_up [held, D, F], w_down [held, F, D].
    → (y [T, D] f32, {"routed_slots", "expert_rows"} int32).

    Two dispatches give the same part:

    * blocks: each held expert runs as one block over every token,
      its output weighted by the token's route to it (0 where none). The
      shapes are static, so the cost does not follow the routing; but it
      computes held·T rows, E/k times the routed slots on average (21× at
      128 experts, top-6), through a [held, T, F] float32 intermediate.
      With 8 held experts over 8,192 tokens it ran faster on a v5e than
      a grouped ``lax.ragged_dot`` dispatch, whose step time also moved
      3 % with the seed's routing.
    * sorted: the T·k slots sorted by held expert, the routed ones
      run through two ``lax.ragged_dot`` calls and added back to their
      tokens: as many rows as routed slots, and a cost that follows the
      routing.

    The layer takes the blocks while their intermediate holds at most
    ``BLOCKS_MAX_ENTRIES`` entries, else sorts: a chip holding many experts
    over a long batch (every expert, as the registry's full Nemotron-H
    holds them) sorts."""
    local = experts - first
    mine = (local >= 0) & (local < held)
    if held * x.shape[0] * params["w_up"].shape[-1] > BLOCKS_MAX_ENTRIES:
        y, rows = _sorted_experts(params, x, weights, local, mine, held)
        return y, {"routed_slots": jnp.sum(mine, dtype=jnp.int32),
                   "expert_rows": rows}
    route_w = jnp.sum(jnp.where(
        mine[..., None] & (local[..., None] == jnp.arange(held)),
        weights[..., None], 0.0), axis=1)                    # [T, held]
    h = jnp.einsum("td,edf->etf", x, params["w_up"],
                   preferred_element_type=jnp.float32)
    h = (_relu2(h) * route_w.T[:, :, None]).astype(x.dtype)
    y = sum(jnp.dot(h[e], params["w_down"][e],
                    preferred_element_type=jnp.float32)
            for e in range(held))
    # rows: every (token, held expert) pair of the blocks, counted on the
    # device like the slots (a folded constant would fall outside the
    # layer's scope)
    return y, {"routed_slots": jnp.sum(mine, dtype=jnp.int32),
               "expert_rows": jnp.sum(route_w >= 0, dtype=jnp.int32)}


def _sorted_experts(params, x: Array, weights: Array, local: Array,
                    mine: Array, held: int):
    """The sorted dispatch of ``held_experts_ffn``. → (y [T, D] f32, rows
    its ragged matmuls computed: the routed slots)."""
    t, k = local.shape
    key = jnp.where(mine, local, held).reshape(-1)      # absent slots last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)                     # [held]
    rows = jnp.sum(sizes)
    live = (jnp.arange(t * k) < rows)[:, None]
    token = order // k
    w = jnp.where(mine, weights, 0.0).reshape(-1)[order]
    h = jax.lax.ragged_dot(x[token], params["w_up"], sizes,
                           preferred_element_type=jnp.float32)
    h = jnp.where(live, _relu2(h) * w[:, None], 0.0).astype(x.dtype)
    out = jax.lax.ragged_dot(h, params["w_down"], sizes,
                             preferred_element_type=jnp.float32)
    y = jnp.zeros((t, x.shape[1]), jnp.float32).at[token].add(
        jnp.where(live, out, 0.0))
    return y, rows


def moe_dropless(params, x: Array, *, top_k: int, first: int, held: int,
                 routed_scale: float):
    """Nemotron-H MoE block on this chip's share of the experts.
    x [B, S, D] → (y [B, S, D], counters).

    params: router [D, E] over all experts; w_up [held, D, F], w_down
    [held, F, D]; shared_up [D, Fs], shared_down [Fs, D] (one shared relu²
    expert, optional)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    weights, experts = route(xt, params["router"], top_k=top_k,
                             routed_scale=routed_scale)
    y, counters = held_experts_ffn(params, xt, weights, experts,
                                   first=first, held=held)
    if "shared_up" in params:
        hs = _relu2(jnp.einsum("td,df->tf", xt, params["shared_up"],
                               preferred_element_type=jnp.float32))
        y = y + jnp.einsum("tf,fd->td", hs.astype(x.dtype),
                           params["shared_down"]).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), counters
