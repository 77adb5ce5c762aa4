"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function defines the exact contract its kernel must meet
(``tests/test_kernels.py`` sweeps shapes × dtypes and asserts allclose).
These are also the implementations used on non-TPU backends and inside the
dry-run/roofline path, where XLA-native HLO keeps ``cost_analysis()``
meaningful (see DESIGN §3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sparsify as sp

Array = jax.Array

SUBLANES = 8
LANES = 1024
BLOCK = SUBLANES * LANES


def ref_count_ge(x: Array, taus: Array) -> Array:
    """counts[j] = #{i : |x_i| >= taus_j}. x: [d] any float dtype; taus: [B] f32."""
    mag = jnp.abs(x.astype(jnp.float32))
    return jnp.sum(mag[:, None] >= taus[None, :], axis=0).astype(jnp.int32)


def ref_sparsify_ef(g: Array, e: Array, mask_in: Array, weight: Array,
                    tau: Array):
    """Fused error-feedback + threshold/mask sparsification.

    g̃ = weight·g + e
    keep = (|g̃| >= tau) | (mask_in > 0)
    ḡ = keep ? g̃ : 0 ;  e' = g̃ − ḡ ;  nnz = #{ḡ ≠ 0}

    Returns (ḡ, e', nnz:int32 scalar). Compute in f32, outputs cast back to
    g.dtype (except nnz).
    """
    gt = (weight.astype(jnp.float32) * g.astype(jnp.float32)
          + e.astype(jnp.float32))
    keep = (jnp.abs(gt) >= tau.astype(jnp.float32)) | (mask_in > 0)
    gbar = jnp.where(keep, gt, 0.0)
    e_new = gt - gbar
    nnz = jnp.sum(gbar != 0).astype(jnp.int32)
    return gbar.astype(g.dtype), e_new.astype(e.dtype), nnz


def ref_chain_accum(gamma_in: Array, gbar: Array):
    """γ_out = γ_in + ḡ ; nnz(γ_out). Returns (γ_out, nnz:int32 scalar)."""
    gamma = (gamma_in.astype(jnp.float32) + gbar.astype(jnp.float32))
    nnz = jnp.sum(gamma != 0).astype(jnp.int32)
    return gamma.astype(gamma_in.dtype), nnz


def ref_cl_fuse(g: Array, e: Array, gamma_in: Array, weight: Array,
                tau: Array):
    """Fused CL-SIA hot path (Alg 3 lines 2–5) in one pass.

    γ̃ = weight·g + e + γ_in
    γ_out = |γ̃| >= tau ? γ̃ : 0 ;  e' = γ̃ − γ_out ;  nnz(γ_out)

    Returns (γ_out, e', nnz:int32 scalar).
    """
    gt = (weight.astype(jnp.float32) * g.astype(jnp.float32)
          + e.astype(jnp.float32) + gamma_in.astype(jnp.float32))
    keep = jnp.abs(gt) >= tau.astype(jnp.float32)
    gamma = jnp.where(keep, gt, 0.0)
    e_new = gt - gamma
    nnz = jnp.sum(gamma != 0).astype(jnp.int32)
    return gamma.astype(gamma_in.dtype), e_new.astype(e.dtype), nnz


# ---------------------------------------------------------------------------
# Batched W-lane level variants (contracts for repro.kernels.level)
# ---------------------------------------------------------------------------

def _apply_valid(valid: Array, *arrays):
    v = (valid > 0)
    out = tuple(jnp.where(v[:, None], a, jnp.zeros_like(a)) for a in arrays)
    return out if len(out) > 1 else out[0]


def ref_err_sq_level(e_new: Array) -> Array:
    """Pinned-order ‖e'‖² per lane — the ``err_sq_mode="kernel"`` contract.

    Summation order (documented, bit-reproducible across backends): each
    zero-padded (SUBLANES, LANES) f32 tile is squared elementwise, folded
    pairwise over lanes (1024 → 512 → … → 1: ``x[:, :n] + x[:, n:2n]``),
    then pairwise over sublanes (8 → 4 → 2 → 1); tile scalars accumulate
    left-to-right in block order. The zero padding is exact (+0 adds are
    identities), but the pairing of real elements depends on the tile
    geometry — this is a *different* (better-conditioned) order than the
    jnp row-sum, hence the opt-in config flag.
    """
    w_lanes, d = e_new.shape
    n_blocks = max(1, -(-d // BLOCK))
    pad = n_blocks * BLOCK - d
    tiles = jnp.pad(e_new.astype(jnp.float32), ((0, 0), (0, pad))).reshape(
        w_lanes, n_blocks, SUBLANES, LANES)
    sq = tiles * tiles
    n = LANES
    while n > 1:
        n //= 2
        sq = sq[..., :n] + sq[..., n:2 * n]
    m = SUBLANES
    while m > 1:
        m //= 2
        sq = sq[..., :m, :] + sq[..., m:2 * m, :]
    per_block = sq[..., 0, 0]                       # [W, n_blocks]
    acc = per_block[:, 0]
    for j in range(1, n_blocks):
        acc = acc + per_block[:, j]
    return acc


def ref_sparsify_ef_level(g, e, mask_in, weight, tau, valid, *,
                          with_err: bool = False):
    """Batched :func:`ref_sparsify_ef`; lanes with ``valid == 0`` output
    zeros (the level schedule's padding slots). ``mask_in`` may be None
    (pure-threshold keep). All counts are int32 [W]. ``with_err`` appends
    the pinned-order ‖e'‖² (:func:`ref_err_sq_level`) as a final [W] f32
    output — the in-kernel ``err_sq_mode="kernel"`` reduction."""
    gt = (weight[:, None].astype(jnp.float32) * g.astype(jnp.float32)
          + e.astype(jnp.float32))
    keep = jnp.abs(gt) >= tau[:, None].astype(jnp.float32)
    if mask_in is not None:
        keep = keep | (mask_in > 0)
    gbar = jnp.where(keep, gt, 0.0)
    e_new = gt - gbar
    gbar, e_new = _apply_valid(valid, gbar, e_new)
    nnz = jnp.sum(gbar != 0, axis=-1).astype(jnp.int32)
    out = (gbar.astype(g.dtype), e_new.astype(e.dtype), nnz)
    return out + (ref_err_sq_level(e_new),) if with_err else out


def _expand_gmask(gmask, lanes: int, gmask_cohorts: int):
    """Cohort-shared [B, d] gmask → per-lane [lanes, d] (cohort-major).

    Broadcast semantics only — values are replicated, so results are
    bitwise identical to the sequential per-cohort [d]-shared call.
    """
    if gmask is None or not gmask_cohorts or gmask.ndim != 2:
        return gmask
    if gmask.shape[0] == lanes:
        return gmask
    return jnp.repeat(gmask, lanes // gmask.shape[0], axis=0)


def ref_chain_accum_level(gamma_in, gbar, valid, gmask=None, *,
                          gmask_cohorts: int = 0):
    """Batched :func:`ref_chain_accum` + off-global-mask support count."""
    gmask = _expand_gmask(gmask, gamma_in.shape[0], gmask_cohorts)
    gamma = gamma_in.astype(jnp.float32) + gbar.astype(jnp.float32)
    gamma = _apply_valid(valid, gamma)
    nz = gamma != 0
    nnz = jnp.sum(nz, axis=-1).astype(jnp.int32)
    if gmask is None:
        nnz_off = nnz
    else:
        nnz_off = jnp.sum(nz & (gmask <= 0), axis=-1).astype(jnp.int32)
    return gamma.astype(gamma_in.dtype), nnz, nnz_off


def ref_cl_fuse_level(g, e, gamma_in, weight, tau, participate, valid,
                      gmask=None, mask_in=None, *, gmask_cohorts: int = 0,
                      with_err: bool = False):
    """Batched complete CL node step (Algorithms 3/5 with stragglers).

    See :func:`repro.kernels.level.cl_fuse_level_pallas` for the math.
    Returns (γ_out [W,d], e' [W,d], nnz [W] i32, nnz_off [W] i32)
    (+ pinned-order ‖e'‖² [W] f32 when ``with_err``).
    """
    gmask = _expand_gmask(gmask, g.shape[0], gmask_cohorts)
    w = weight[:, None].astype(jnp.float32)
    p = participate[:, None].astype(jnp.float32)
    gt = w * g.astype(jnp.float32) + e.astype(jnp.float32)
    gin = gamma_in.astype(jnp.float32)
    s = p * gt + gin
    lam_t = (1.0 - gmask) * s if gmask is not None else s
    keep = jnp.abs(lam_t) >= tau[:, None].astype(jnp.float32)
    if mask_in is not None:
        keep = keep | (mask_in > 0)
    lam = jnp.where(keep, lam_t, 0.0)
    e_new = lam_t - lam
    gamma = (gmask * s + lam) if gmask is not None else lam
    alive = p > 0
    gamma = jnp.where(alive, gamma, gin)
    e_new = jnp.where(alive, e_new, gt)
    gamma, e_new = _apply_valid(valid, gamma, e_new)
    nz = gamma != 0
    nnz = jnp.sum(nz, axis=-1).astype(jnp.int32)
    if gmask is None:
        nnz_off = nnz
    else:
        nnz_off = jnp.sum(nz & (gmask <= 0), axis=-1).astype(jnp.int32)
    out = (gamma.astype(gamma_in.dtype), e_new.astype(e.dtype), nnz,
           nnz_off)
    return out + (ref_err_sq_level(e_new),) if with_err else out


def ref_count_ge_level(x: Array, taus: Array) -> Array:
    """counts[w, b] = #{i : |x_{w,i}| >= taus_{w,b}}; x [W,d], taus [W,B]."""
    mag = jnp.abs(x.astype(jnp.float32))
    return jnp.sum(mag[:, :, None] >= taus[:, None, :],
                   axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused-operand τ search (contracts for the count_ge_fused / hist kernels)
# ---------------------------------------------------------------------------

def fused_operand(g, e, gamma_in, weight, participate, gmask=None, *,
                  include_gamma: bool = False, gmask_cohorts: int = 0):
    """The bisection operand reconstructed from raw node inputs (f32 [W,d]).

    Covers all five algorithms' sparsifier operands:

    * SIA / RE-SIA:  ``w·g + e``                  (include_gamma=False)
    * CL-SIA:        ``p·(w·g + e) + γ_in``       (include_gamma=True)
    * TC-SIA:        ``(1−m)·(w·g + e)``          (gmask given)
    * CL-TC-SIA:     ``(1−m)·(p·(w·g + e) + γ_in)``

    Same float expressions as the materialized jnp path in
    ``repro.core.algorithms`` — the kernels mirror these per tile. Inputs
    in the kernels' block layout ([W, n_blocks, SUBLANES, LANES], the
    gmask [n_blocks, SUBLANES, LANES]) give the operand in it.
    """
    lane = (slice(None),) + (None,) * (g.ndim - 1)
    w = weight[lane].astype(jnp.float32)
    s = w * g.astype(jnp.float32) + e.astype(jnp.float32)
    if include_gamma:
        s = (participate[lane].astype(jnp.float32) * s
             + gamma_in.astype(jnp.float32))
    if gmask is not None:
        gm = _expand_gmask(gmask, g.shape[0], gmask_cohorts)
        s = (1.0 - gm) * s
    return s


def ref_count_ge_fused_level(g, e, gamma_in, weight, participate, taus,
                             gmask=None, *, include_gamma: bool = False,
                             gmask_cohorts: int = 0) -> Array:
    """Batched fused-operand counts ([W,d] inputs, taus [W,B] → i32 [W,B]).

    Per-lane ``taus`` must be nondecreasing (the bisection brackets always
    are): the reference counts via
    :func:`repro.core.sparsify.count_ge_sorted_batch`, whose integers are
    bit-identical to the O(d·B) broadcast.
    """
    op = fused_operand(g, e, gamma_in, weight, participate, gmask,
                       include_gamma=include_gamma,
                       gmask_cohorts=gmask_cohorts)
    return sp.count_ge_sorted_batch(jnp.abs(op), taus)


def ref_hist_topq_level(g, e, gamma_in, weight, participate, tables,
                        gmask=None, *, include_gamma: bool = False,
                        gmask_cohorts: int = 0):
    """Fused-operand joint digit histogram (tau_impl="hist") reference.

    ``tables = (tau1, new_lo, w2, top_shift)`` per lane ([W, ·] each, from
    ``repro.core.sparsify._hist_tables``); returns ``(D2 [W, b+1, b+1],
    F [W, b+1])`` int32 — see :func:`repro.core.sparsify._hist_digits`.
    """
    op = fused_operand(g, e, gamma_in, weight, participate, gmask,
                       include_gamma=include_gamma,
                       gmask_cohorts=gmask_cohorts)
    return jax.vmap(sp._hist_digits)(jnp.abs(op), *tables)
