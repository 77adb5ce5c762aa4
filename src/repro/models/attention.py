"""Attention: GQA/MQA with RoPE; full, blocked (flash-style), SWA, decode.

Pure-jnp implementations — GSPMD shards them (heads→model, batch→data,
cache-seq→data for long-context decode; see models/partition.py). The
blocked path is the memory-bounded O(S²) streaming softmax used for ≥8k
sequences (tiles never materialize the full score matrix); tests prove
blocked ≡ plain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_rope

Array = jax.Array

NEG_INF = -1e30


def _split_gqa(q: Array, num_kv: int) -> Array:
    """[B, S, Hq, Dh] → [B, S, Hkv, G, Dh]."""
    b, s, hq, dh = q.shape
    return q.reshape(b, s, num_kv, hq // num_kv, dh)


def _mesh_auto() -> dict:
    """{axis_name: size} for the *auto* axes of the current abstract mesh.

    Manual axes (e.g. `data` inside the train step's phase-1 shard_map)
    must never appear in a sharding constraint; auto axes (pjit-land
    serve/prefill paths) must be pinned explicitly or GSPMD will
    un-shard the batch inside attention loops (measured: 36 TB/step of
    batch all-gathers on granite prefill_32k; EXPERIMENTS §Perf it.8)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return {n: mesh.shape[n] for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}


def _head_axes(hkv: int, g: int):
    """Pick which of (kv, group) head dims shards over `model` (divisible
    one wins; None if neither). GSPMD drops head sharding through the GQA
    reshape in the attention backward — without an explicit constraint the
    S×S score tensors materialize with heads replicated (measured 51 GB/op
    on granite-34b; EXPERIMENTS §Perf it.5)."""
    m = _mesh_auto().get("model", 1)
    if m <= 1:
        return None, None
    if hkv % m == 0:
        return "model", None
    if g % m == 0:
        return None, "model"
    return None, None


def _batch_ax(b: int):
    """Auto DP axes to pin the batch dim to (None inside manual-dp code)."""
    auto = _mesh_auto()
    dp = tuple(a for a in ("pod", "data") if auto.get(a, 1) > 1)
    if not dp:
        return None
    tot = 1
    for a in dp:
        tot *= auto[a]
    return dp if b % tot == 0 else None


def _constrain_scores(s: Array) -> Array:
    """s: [B, Hkv, G, Sq, Sk] — pin batch + head sharding; when no head dim
    divides the model axis (phi4 24H, musicgen 24H, llama4 40H), fall back
    to sharding the query-sequence dim (sequence-parallel attention) so the
    S×S score tensors never replicate (peak 130–340 GB/dev before this;
    EXPERIMENTS §Perf it.7)."""
    b_ax = _batch_ax(s.shape[0])
    kv_ax, g_ax = _head_axes(s.shape[1], s.shape[2])
    sq_ax = None
    if kv_ax is None and g_ax is None:
        m = _mesh_auto().get("model", 1)
        if m > 1 and s.shape[3] % m == 0:
            sq_ax = "model"
    if b_ax is None and kv_ax is None and g_ax is None and sq_ax is None:
        return s
    return jax.lax.with_sharding_constraint(
        s, P(b_ax, kv_ax, g_ax, sq_ax, None))


def plain_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, q_offset: int = 0,
                    k_offset: int | Array = 0) -> Array:
    """Materialized-scores attention (used for S ≤ ~4k and as the oracle).

    q: [B, Sq, Hq, Dh]; k,v: [B, Sk, Hkv, Dh]. ``q_offset``/``k_offset``
    are the absolute positions of q[0]/k[0] (cached decoding, chunked
    prefill, SWA-sliced K spans).
    """
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    qg = _split_gqa(q, hkv)
    kv_ax, g_ax = _head_axes(hkv, hq // hkv)
    b_ax = _batch_ax(b)
    if b_ax is not None or kv_ax is not None or g_ax is not None:
        qg = jax.lax.with_sharding_constraint(
            qg, P(b_ax, None, kv_ax, g_ax, None))
    scale = dh ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = _constrain_scores(s)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = k_offset + jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = _constrain_scores(jax.nn.softmax(s, axis=-1))
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def blocked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      window: int = 0, q_chunk: int = 1024,
                      k_chunk: int = 1024, q_offset: int = 0,
                      remat: bool = False) -> Array:
    """Q-blocked attention: scan over query chunks, each attending to the
    full K/V with materialized [qc × Sk] scores (O(qc·Sk) memory).

    Matches :func:`plain_attention` to f32 accuracy. A doubly-blocked
    flash-style inner KV loop was tried first and abandoned: GSPMD reshards
    the streaming-softmax carries on every inner step (measured 90112 ×
    score-sized all-gathers = 36 TB/step on granite prefill_32k;
    EXPERIMENTS §Perf it.8) — one loop level keeps shardings stable, and
    the true VMEM-tiled flash form belongs in a Pallas kernel, not XLA
    loops. ``k_chunk`` is accepted for API compatibility. ``remat``
    recomputes each block's scores in the backward pass, so it keeps one
    block's [qc × Sk] scores live instead of every block's.
    """
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    nq = sq // q_chunk
    qb = jnp.moveaxis(q.reshape(b, nq, q_chunk, hq, dh), 1, 0)

    # SWA: each q block only sees the last (window + q_chunk) keys — slice
    # that span instead of scoring all Sk (6.4× attention-FLOP cut on
    # mixtral prefill_32k; EXPERIMENTS §Perf it.B2).
    span = window + q_chunk if window > 0 else sk
    span = min(span, sk)

    def q_block(_, xs):
        qi, qblk = xs
        q_off = q_offset + qi * q_chunk
        if span < sk:
            start = jnp.clip(q_off + q_chunk - span, 0, sk - span)
            kblk = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
            out = plain_attention(qblk, kblk, vblk, causal=causal,
                                  window=window, q_offset=q_off,
                                  k_offset=start)
        else:
            out = plain_attention(qblk, k, v, causal=causal, window=window,
                                  q_offset=q_off)
        return None, out

    if remat:
        q_block = jax.checkpoint(q_block)
    outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))[1]
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, hq, dh).astype(q.dtype)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array,
                     *, window: int = 0, ring: bool = False) -> Array:
    """One-token attention against a cache.

    q: [B, 1, Hq, Dh]; caches: [B, Smax, Hkv, Dh]; ``pos``: current absolute
    position (scalar int32). Plain cache: entries at index ≤ pos are valid.
    Ring cache (``ring=True``): slot j holds absolute position
    pos − ((pos − j) mod Smax); valid iff j ≤ pos (warmup) — window bound is
    implicit.
    """
    b, _, hq, dh = q.shape
    _, smax, hkv, _ = k_cache.shape
    qg = _split_gqa(q, hkv).astype(jnp.float32) * dh ** -0.5
    s = _constrain_scores(
        jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache.astype(jnp.float32)))
    kpos = jnp.arange(smax)
    valid = kpos <= pos
    if window > 0 and not ring:
        valid &= kpos > pos - window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention sub-layer (projections + RoPE + cache plumbing)
# ---------------------------------------------------------------------------

def attn_project_qkv(params, x: Array, *, num_heads: int, num_kv: int,
                     head_dim: int, rope_theta: float, positions: Array,
                     rope: bool = True):
    """x: [B, S, D] → q [B,S,Hq,Dh], k,v [B,S,Hkv,Dh], RoPE applied
    (unless ``rope`` is off)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, s, num_kv, head_dim)
    v = v.reshape(b, s, num_kv, head_dim)
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attn_out(params, o: Array) -> Array:
    b, s, h, dh = o.shape
    return jnp.einsum("bsh,hd->bsd", o.reshape(b, s, h * dh), params["wo"])


def run_attention(params, x: Array, *, cfg_heads: int, cfg_kv: int,
                  head_dim: int, rope_theta: float, window: int,
                  cache=None, pos=None, blocked_threshold: int = 8192,
                  q_chunk: int = 1024, k_chunk: int = 1024,
                  rope: bool = True, remat_blocks: bool = False):
    """Full attention sub-layer.

    Modes:
    * train/prefill: ``cache is None`` → causal self-attention over x; if a
      cache dict is passed with ``pos is None`` the new K/V are returned for
      cache seeding (prefill).
    * decode: ``cache`` + scalar ``pos`` → one-token step, cache updated.

    Returns (out [B,S,D], new_cache_or_None).
    """
    b, s, _ = x.shape
    if pos is None:
        positions = jnp.arange(s)[None, :]
    else:
        positions = jnp.full((b, s), pos)[..., :]
    q, k, v = attn_project_qkv(
        params, x, num_heads=cfg_heads, num_kv=cfg_kv, head_dim=head_dim,
        rope_theta=rope_theta, positions=positions, rope=rope)

    if cache is not None and pos is not None:
        # decode step. SWA caches are ring buffers of length == window:
        # slot = pos % smax; validity slot_pos <= pos covers both the warmup
        # and the steady state, and the window bound is implicit for ring
        # buffers (only the last `window` tokens are retained).
        smax = cache["k"].shape[1]
        slot = pos % smax
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
        eff_window = window if (window == 0 or smax > window) else 0
        o = decode_attention(q, k_cache, v_cache, pos, window=eff_window,
                             ring=smax <= max(window, 0) and window > 0)
        return attn_out(params, o), {"k": k_cache, "v": v_cache}

    if s >= blocked_threshold:
        o = blocked_attention(q, k, v, causal=True, window=window,
                              q_chunk=q_chunk, k_chunk=k_chunk,
                              remat=remat_blocks)
    else:
        o = plain_attention(q, k, v, causal=True, window=window)
    new_cache = None
    if cache is not None:
        smax = cache["k"].shape[1]
        if smax < s:
            # SWA ring cache shorter than the prompt: keep the last smax
            # tokens; slot alignment requires s % smax == 0 (configs comply).
            assert s % smax == 0, (s, smax)
            kc, vc = k[:, -smax:], v[:, -smax:]
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)
        new_cache = {"k": kc.astype(cache["k"].dtype),
                     "v": vc.astype(cache["v"].dtype)}
    return attn_out(params, o), new_cache
