"""Mamba2 (SSD — state-space duality) block, chunked scan + decode step.

Implements the SSD recurrence
    h_t = exp(Δ_t·A) · h_{t-1} + Δ_t · B_t ⊗ x_t        (h: [H, P, N])
    y_t = C_t · h_t + D ⊙ x_t
with the chunked dual form (intra-chunk quadratic + inter-chunk state scan),
following Dao & Gu 2024 [arXiv:2405.21060]. B and C come in one group
shared by every head ([B, L, N]) or in G groups ([B, L, G, N]), head h
reading group h // (H/G). ``naive_ssd`` is the step-by-step recurrence
oracle used by tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import causal_conv1d, rms_norm

Array = jax.Array


def _segsum(z: Array) -> Array:
    """Lower-triangular pairwise cumulative sums.

    z: [..., C] → out[..., i, j] = Σ_{k=j+1..i} z_k  (−inf above diagonal).
    """
    c = z.shape[-1]
    cs = jnp.cumsum(z, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((c, c), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: Array, dt: Array, a_log: Array, b: Array, c: Array,
                chunk: int):
    """Chunked SSD. Shapes:
    x:  [B, L, H, P]   (pre-discretization input)
    dt: [B, L, H]      (positive step sizes, post-softplus)
    a_log: [H]         (A = −exp(a_log) < 0)
    b, c: [B, L, N]    (single group, shared across heads) or
          [B, L, G, N] (G groups; one group is the single-group program)

    Returns (y [B, L, H, P], final_state [B, H, P, N]). L % chunk == 0.
    """
    if b.ndim == 4:
        if b.shape[2] == 1:
            return ssd_chunked(x, dt, a_log, b[:, :, 0], c[:, :, 0], chunk)
        return _ssd_chunked_grouped(x, dt, a_log, b, c, chunk)
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    nc = l // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))                    # [H]

    xf = x.astype(jnp.float32) * dt[..., None]                 # Δx
    da = dt.astype(jnp.float32) * a                            # [B, L, H]

    xc = xf.reshape(bsz, nc, chunk, h, p)
    dac = da.reshape(bsz, nc, chunk, h)
    bc = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cc = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    da_cum = jnp.cumsum(dac, axis=2)                           # [B,nc,C,H]

    # --- intra-chunk (diagonal blocks): y_ij = C_i·B_j · exp(Σ_{j<k<=i} da)
    ldec = jnp.exp(_segsum(jnp.moveaxis(dac, 3, 2)))           # [B,nc,H,C,C]
    scores = jnp.einsum("bzin,bzjn->bzij", cc, bc)             # [B,nc,C,C]
    att = scores[:, :, None] * ldec                            # [B,nc,H,C,C]
    y_diag = jnp.einsum("bzhij,bzjhp->bzihp", att, xc)

    # --- chunk summary states: S_z = Σ_j exp(da_cum[-1]−da_cum[j])·B_j⊗x_j
    decay_states = jnp.exp(da_cum[:, :, -1:, :] - da_cum)      # [B,nc,C,H]
    states = jnp.einsum("bzcn,bzch,bzchp->bzhpn", bc, decay_states, xc)

    # --- inter-chunk recurrence
    chunk_decay = jnp.exp(da_cum[:, :, -1])                    # [B,nc,H]

    def step(s_prev, inp):
        dec, st = inp                                          # [B,H], [B,H,P,N]
        s = s_prev * dec[..., None, None] + st
        return s, s_prev

    s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                      # [B,nc,H,P,N]

    # --- inter-chunk contribution: y_i += C_i · exp(da_cum[i]) · S_prev
    state_decay = jnp.exp(da_cum)                              # [B,nc,C,H]
    y_off = jnp.einsum("bzcn,bzhpn,bzch->bzchp", cc, s_prevs, state_decay)

    y = (y_diag + y_off).reshape(bsz, l, h, p)
    return y.astype(x.dtype), s_final


def _ssd_chunked_grouped(x: Array, dt: Array, a_log: Array, b: Array,
                         c: Array, chunk: int):
    """``ssd_chunked`` with B and C in G > 1 groups: the heads split
    [G, H/G] and each group's C·Bᵀ scores serve its H/G heads."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    nc = l // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))                    # [H]

    xf = x.astype(jnp.float32) * dt[..., None]                 # Δx
    da = dt.astype(jnp.float32) * a                            # [B, L, H]

    xc = xf.reshape(bsz, nc, chunk, g, hg, p)
    dac = da.reshape(bsz, nc, chunk, g, hg)
    bc = b.astype(jnp.float32).reshape(bsz, nc, chunk, g, n)
    cc = c.astype(jnp.float32).reshape(bsz, nc, chunk, g, n)

    da_cum = jnp.cumsum(dac, axis=2)                           # [B,nc,C,G,Hg]

    # intra-chunk: y_ij = C_i·B_j · exp(Σ_{j<k<=i} da), per group
    ldec = jnp.exp(_segsum(jnp.moveaxis(dac, 2, 4)))          # [B,nc,G,Hg,C,C]
    scores = jnp.einsum("bzign,bzjgn->bzgij", cc, bc)          # [B,nc,G,C,C]
    att = scores[:, :, :, None] * ldec                        # [B,nc,G,Hg,C,C]
    y_diag = jnp.einsum("bzghij,bzjghp->bzighp", att, xc)

    # chunk summary states
    decay_states = jnp.exp(da_cum[:, :, -1:] - da_cum)         # [B,nc,C,G,Hg]
    states = jnp.einsum("bzcgn,bzcgh,bzcghp->bzghpn", bc, decay_states, xc)

    chunk_decay = jnp.exp(da_cum[:, :, -1])                    # [B,nc,G,Hg]

    def step(s_prev, inp):
        dec, st = inp                          # [B,G,Hg], [B,G,Hg,P,N]
        s = s_prev * dec[..., None, None] + st
        return s, s_prev

    s0 = jnp.zeros((bsz, g, hg, p, n), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)))
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                     # [B,nc,G,Hg,P,N]

    state_decay = jnp.exp(da_cum)                              # [B,nc,C,G,Hg]
    y_off = jnp.einsum("bzcgn,bzghpn,bzcgh->bzcghp", cc, s_prevs,
                       state_decay)

    y = (y_diag + y_off).reshape(bsz, l, h, p)
    return y.astype(x.dtype), s_final.reshape(bsz, h, p, n)


def _per_head(bc: Array, h: int) -> Array:
    """[..., N] (one group) or [..., G, N] → [..., H, N]: head h reads
    group h // (H/G)."""
    if bc.ndim == 2:                                           # [B, N]
        return jnp.broadcast_to(bc[:, None], (bc.shape[0], h, bc.shape[-1]))
    return jnp.repeat(bc, h // bc.shape[1], axis=1)


def naive_ssd(x: Array, dt: Array, a_log: Array, b: Array, c: Array):
    """Step-by-step recurrence oracle (tests only; O(L) sequential).
    b, c: [B, L, N] or [B, L, G, N]."""
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))

    def step(s, inp):
        xt, dtt, bt, ct = inp
        dec = jnp.exp(dtt * a)                                 # [B,H]
        s = (s * dec[..., None, None]
             + jnp.einsum("bh,bhp,bhn->bhpn", dtt, xt, _per_head(bt, h)))
        y = jnp.einsum("bhn,bhpn->bhp", _per_head(ct, h), s)
        return s, y

    s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(b.astype(jnp.float32), 1, 0),
          jnp.moveaxis(c.astype(jnp.float32), 1, 0))
    s_final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), s_final


def ssd_decode_step(state: Array, xt: Array, dtt: Array, a_log: Array,
                    bt: Array, ct: Array):
    """One-token SSD update. state: [B,H,P,N]; xt: [B,H,P]; dtt: [B,H];
    bt, ct: [B,N] or [B,G,N]. Returns (y [B,H,P], new_state)."""
    if bt.ndim == 3:
        h = xt.shape[1]
        a = -jnp.exp(a_log.astype(jnp.float32))
        dec = jnp.exp(dtt.astype(jnp.float32) * a)
        state = (state * dec[..., None, None]
                 + jnp.einsum("bh,bhp,bhn->bhpn", dtt.astype(jnp.float32),
                              xt.astype(jnp.float32),
                              _per_head(bt.astype(jnp.float32), h)))
        y = jnp.einsum("bhn,bhpn->bhp", _per_head(ct.astype(jnp.float32), h),
                       state)
        return y.astype(xt.dtype), state
    a = -jnp.exp(a_log.astype(jnp.float32))
    dec = jnp.exp(dtt.astype(jnp.float32) * a)
    state = (state * dec[..., None, None]
             + jnp.einsum("bh,bhp,bn->bhpn", dtt.astype(jnp.float32),
                          xt.astype(jnp.float32), bt.astype(jnp.float32)))
    y = jnp.einsum("bn,bhpn->bhp", ct.astype(jnp.float32), state)
    return y.astype(xt.dtype), state


# ---------------------------------------------------------------------------
# Full Mamba2 block (projections + conv + SSD + gated norm)
# ---------------------------------------------------------------------------

def mamba2_split(cfg, zxbcdt: Array):
    di, n, h = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads
    z, x, b, c, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    return z, x, b, c, dt


def gated_norm(y: Array, z: Array, scale: Array, groups: int,
               eps: float) -> Array:
    """RMSNorm of y·silu(z), each of ``groups`` equal channel groups
    normalised on its own (Nemotron-H's gated norm); one group is the
    plain full-width norm."""
    yz = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    if groups == 1:
        return rms_norm(yz, scale, eps)
    shape = yz.shape
    yg = yz.astype(jnp.float32).reshape(*shape[:-1], groups, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return (yg.reshape(shape) * scale.astype(jnp.float32)).astype(y.dtype)


def mamba2_block(params, cfg, u: Array, cache=None):
    """u: [B, L, D] → (y [B, L, D], new_cache).

    cache = {"conv": [B, k-1, d_conv], "state": [B, H, P, N]} for decode
    (L == 1) and prefill seeding; None for pure training forward.
    """
    bsz, l, _ = u.shape
    di, h, p = cfg.d_inner, cfg.ssm_heads, cfg.ssm_headdim
    g, n = cfg.ssm_groups, cfg.ssm_groups * cfg.ssm_state

    zxbcdt = jnp.einsum("bld,de->ble", u, params["in_proj"])
    z, x, b, c, dt = mamba2_split(cfg, zxbcdt)

    xbc = jnp.concatenate([x, b, c], axis=-1)
    conv_cache = None if cache is None else cache["conv"]
    xbc, new_conv = causal_conv1d(xbc, params["conv_w"], conv_cache)
    if "conv_b" in params:
        xbc = xbc + params["conv_b"].astype(xbc.dtype)
    xbc = jax.nn.silu(xbc)
    x, b, c = jnp.split(xbc, [di, di + n], axis=-1)
    if g > 1:
        b = b.reshape(bsz, l, g, cfg.ssm_state)
        c = c.reshape(bsz, l, g, cfg.ssm_state)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # [B,L,H]
    xh = x.reshape(bsz, l, h, p)

    if cache is not None and l == 1:
        y, new_state = ssd_decode_step(
            cache["state"], xh[:, 0], dt[:, 0], params["a_log"],
            b[:, 0], c[:, 0])
        y = y[:, None]                                     # [B,1,H,P]
    else:
        # pad L to a chunk multiple with dt=0 steps: exp(0·A)=1 decay and
        # 0·B·x input leave the final state exact; padded outputs sliced off
        pad = (-l) % cfg.ssm_chunk
        if pad:
            xh_p = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            bc_pad = ((0, 0), (0, pad)) + ((0, 0),) * (b.ndim - 2)
            b_p = jnp.pad(b, bc_pad)
            c_p = jnp.pad(c, bc_pad)
            y, new_state = ssd_chunked(xh_p, dt_p, params["a_log"], b_p,
                                       c_p, cfg.ssm_chunk)
            y = y[:, :l]
        else:
            y, new_state = ssd_chunked(xh, dt, params["a_log"], b, c,
                                       cfg.ssm_chunk)

    y = y + xh * params["d_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(bsz, l, di)
    y = gated_norm(y, z, params["norm"], g, cfg.norm_eps)
    out = jnp.einsum("ble,ed->bld", y, params["out_proj"])
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "state": new_state}
    return out, new_cache


def mamba2_init(key, cfg, dtype):
    from repro.models.layers import dense_init
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    n = cfg.ssm_groups * cfg.ssm_state
    keys = jax.random.split(key, 3)
    e_out = 2 * di + 2 * n + h
    p = {
        "in_proj": dense_init(keys[0], d, e_out, dtype),
        "conv_w": (jax.random.normal(keys[1], (cfg.ssm_conv, di + 2 * n),
                                     jnp.float32) * 0.2).astype(dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),             # A = −1
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(keys[2], di, d, dtype),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((di + 2 * n,), dtype)
    return p


def mamba2_cache_init(cfg, batch: int, dtype):
    di, n = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di + 2 * n), dtype),
        "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_headdim, n),
                           jnp.float32),
    }
