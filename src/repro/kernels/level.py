"""Pallas TPU kernels: batched W-lane node-step stages (one pass per level).

The aggregation executors run up to W tree nodes concurrently per level
(the padded ``(L, W)`` schedule of :class:`repro.agg.plan.AggPlan`). These
kernels fuse a **whole level**: inputs carry a leading lane axis
``[W, d]``, per-lane scalars (weight, τ, participate) ride in as ``[W]``
vectors held whole in SMEM, and the grid is ``(W, blocks)`` so every lane
streams its d-vector tile by tile in one ``pallas_call`` — no ``vmap``
over per-node kernels, no per-lane dispatch overhead. A single node is the
``W = 1`` case.

Per-lane counts (nnz, off-mask nnz, candidate counts) and ‖e'‖² gather in
``(SUBLANES, VREG)`` VMEM accumulator tiles that stay resident across the
block axis; the wrappers reduce them. Every block is either the whole
array in its last two dimensions or (8, 128)-aligned, which is what the
TPU compiler accepts.

Padding lanes (``valid == 0`` — the schedule's no-op slots) skip the
elementwise math entirely (``pl.when``) and write zeros, which keeps the
executors' masked scatter-adds no-ops. The DMA for a skipped lane still
runs (block specs are static); the saved work is the VPU math and the
output traffic semantics stay identical to computing on the zero dummy row.

Cohort batching (multi-tenant rounds) flattens B shape-identical levels
into one launch: lanes are laid out cohort-major (``[B*W, d]`` — cohort b
owns lanes ``b*W .. (b+1)*W-1``) so the same ``(lanes, blocks)`` grid
serves all B cohorts in a **single** ``pallas_call``. Per-cohort TC global
masks stay compact ``[B, d]`` in HBM: ``gmask_cohorts=B`` selects a
cohort-shared block spec whose index map sends lane ``w`` to tile
``w // (lanes // B)`` — no ``[B*W, d]`` broadcast, no vmap-of-pallas_call.

``cl_fuse_level`` is the whole CL-family node step (Algorithms 3 and 5,
stragglers included) in a single pass:

    g̃   = w·g + e
    s    = p·g̃ + γ_in            (p ∈ {0,1}: participation)
    Γ    = m·s                    (m: TCS global mask; 0 for Alg 3)
    Λ̃   = (1−m)·s
    keep = |Λ̃| ≥ τ  ∨  mask_in   (τ-sparsifier or precomputed exact mask)
    Λ    = keep ? Λ̃ : 0
    e′   = Λ̃ − Λ
    γ    = Γ + Λ                  (Alg 3: γ = Λ)
    γ_out, e′ = p>0 ? (γ, e′) : (γ_in, g̃)     (straggler forwarding)
    nnz  = #{γ_out ≠ 0};  nnz_off = #{γ_out ≠ 0 ∧ m = 0}

reading (g, e, γ_in[, m, mask_in]) and writing (γ_out, e′) in a single
sweep — the unfused jnp chain takes one sweep per op (per-algorithm
totals: ``benchmarks/bench_round.py::vector_passes``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import scopes

SUBLANES = 8
LANES = 1024
BLOCK = SUBLANES * LANES
VREG = 128                  # lanes of one vector register


# The wrappers' pads into the kernels' blocks and the slices back out run
# under the kernel_layout scope; the pallas_calls stay in the caller's.
_layout_scope = jax.named_scope(scopes.KERNEL_LAYOUT)


@_layout_scope
def _pad_lanes(v: jax.Array, n_blocks: int, pad: int):
    """[W, d] → [W, n_blocks, SUBLANES, LANES] (zero padded)."""
    w = v.shape[0]
    return jnp.pad(v, ((0, 0), (0, pad))).reshape(
        w, n_blocks, SUBLANES, LANES)


@_layout_scope
def _pad_shared(v: jax.Array, n_blocks: int, pad: int):
    """[d] → [n_blocks, SUBLANES, LANES] (zero padded) — a lane-shared
    operand streamed once per block instead of once per (lane, block)."""
    return jnp.pad(v, (0, pad)).reshape(n_blocks, SUBLANES, LANES)


def _geometry(d: int):
    n_blocks = max(1, -(-d // BLOCK))
    return n_blocks, n_blocks * BLOCK - d


def _blk():
    return pl.BlockSpec((1, 1, SUBLANES, LANES), lambda w, j: (w, j, 0, 0))


def _blk_shared():
    # block index ignores the lane axis w: every lane of a level reads the
    # SAME [SUBLANES, LANES] tile — the TC global mask is stored once, [d],
    # never broadcast to [W, d] in HBM (ROADMAP open-item tail)
    return pl.BlockSpec((1, SUBLANES, LANES), lambda w, j: (j, 0, 0))


def _blk_cohort(lanes_per_cohort: int):
    # block index maps lane w to its cohort w // lanes_per_cohort: with
    # lanes flattened cohort-major, every lane of a cohort reads the SAME
    # tile of that cohort's [d] mask — stored once per cohort as [B, d],
    # never broadcast to [B*W, d] in HBM
    return pl.BlockSpec((1, 1, SUBLANES, LANES),
                        lambda w, j: (w // lanes_per_cohort, j, 0, 0))


def _lane():
    # a per-lane scalar table ([W] or [W, n]) lives whole in SMEM; the
    # kernel reads its own lane's entries with ``ref[w]`` / ``ref[w, i]``
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _acc(*lead: int):
    # per-lane (SUBLANES, VREG) accumulator tile(s), resident across the
    # block axis j and reduced in the wrapper — no scalar store per tile
    zeros = (0,) * (len(lead) + 2)
    return pl.BlockSpec((1, *lead, SUBLANES, VREG),
                        lambda w, j: (w, *zeros))


def _acc_shape(w_lanes: int, *lead: int, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((w_lanes, *lead, SUBLANES, VREG), dtype)


def _tile(ref):
    """The current (SUBLANES, LANES) f32 tile of a blocked operand."""
    return ref[...].reshape(SUBLANES, LANES).astype(jnp.float32)


def _store(ref, x):
    ref[...] = x.reshape(ref.shape).astype(ref.dtype)


def _fold(x):
    """(SUBLANES, LANES) → (SUBLANES, VREG): sum of the aligned column
    groups; used for integer counts, whose order does not matter."""
    acc = x[:, :VREG]
    for k in range(VREG, LANES, VREG):
        acc = acc + x[:, k:k + VREG]
    return acc


def _count(pred):
    return _fold(pred.astype(jnp.int32))


@_layout_scope
def _counts(acc):
    """Per-lane totals of ``_count`` accumulator tiles."""
    return jnp.sum(acc, axis=(-2, -1), dtype=jnp.int32)


@_layout_scope
def _lane_err(acc):
    """Per-lane ‖e'‖² of :func:`_pinned_tile_err` tiles (every element of
    a lane's tile holds its sum)."""
    return acc[:, 0, 0]


@_layout_scope
def _unpad(x, w_lanes: int, d: int):
    """[W, n_blocks, SUBLANES, LANES] → [W, d]: the inverse of
    :func:`_pad_lanes`."""
    return x.reshape(w_lanes, -1)[:, :d]


@_layout_scope
def _gmask_operand(gmask, w_lanes: int, gmask_cohorts: int, n_blocks: int,
                   pad: int):
    """Pick the (padded operand, block spec) for a TC global mask.

    [d] → lane-shared; [B, d] with ``gmask_cohorts == B`` → cohort-shared
    (requires ``w_lanes % B == 0``); [W, d] → per-lane.
    """
    if gmask.ndim == 1:
        return _pad_shared(gmask.astype(jnp.float32), n_blocks, pad), \
            _blk_shared()
    if gmask_cohorts:
        if gmask.shape[0] != gmask_cohorts or w_lanes % gmask_cohorts:
            raise ValueError(
                f"cohort gmask {gmask.shape} incompatible with "
                f"{w_lanes} lanes / {gmask_cohorts} cohorts")
        return _pad_lanes(gmask.astype(jnp.float32), n_blocks, pad), \
            _blk_cohort(w_lanes // gmask_cohorts)
    return _pad_lanes(gmask.astype(jnp.float32), n_blocks, pad), _blk()


@_layout_scope
def _drop_pad_level(counts, taus, pad: int):
    """Subtract the zero-padding contribution from per-lane counts.

    Pad elements reconstruct to exactly 0.0 under every operand form
    (g = e = γ_in = m = 0 ⇒ w·0+0 = 0, p·0+0 = 0, (1−0)·0 = 0), so they
    inflate ``counts[w, b]`` by ``pad`` iff ``taus[w, b] <= 0``. The
    bisection brackets are strictly positive (no-op there), but the
    exclusion is enforced here, not just asserted in tests."""
    if pad == 0:
        return counts
    return counts - jnp.where(taus <= 0, jnp.int32(pad), jnp.int32(0))


def _pinned_tile_err(sq):
    """Pairwise-fold an (SUBLANES, LANES) tile of squares to its sum.

    The documented ``err_sq_mode="kernel"`` summation order: lanes fold
    pairwise 1024 → 512 → … → 1 (``x[:, :n] + x[:, n:2n]``), then sublanes
    8 → 4 → 2 → 1; block scalars accumulate left-to-right in grid order.

    Folds down to one vector register use aligned slices. Below that, a
    rotation by the half-width ``n`` adds element ``i ± n`` to element
    ``i``: the row is periodic in ``2n`` after the previous step, so both
    rotation directions give ``x[i mod 2n] + x[(i + n) mod 2n]`` — the
    reference's pairs, in every lane. Returns a (SUBLANES, VREG) tile whose
    every element holds the tile's sum.
    """
    n = LANES
    while n > VREG:
        n //= 2
        sq = sq[:, :n] + sq[:, n:2 * n]
    while n > 1:
        n //= 2
        sq = sq + pltpu.roll(sq, n, 1)
    m = SUBLANES
    while m > 1:
        m //= 2
        sq = sq + pltpu.roll(sq, m, 0)
    return sq


# ---------------------------------------------------------------------------
# sparsify_ef_level — Algs 1/2/4 EF + sparsify stage, one pass per level
# ---------------------------------------------------------------------------

def _sparsify_ef_level_kernel(g_ref, e_ref, w_ref, tau_ref, v_ref, *rest,
                              has_mask: bool, with_err: bool):
    if has_mask:
        m_ref, *rest = rest
    if with_err:
        *rest, err_ref = rest
    gbar_ref, enew_ref, nnz_ref = rest
    lane, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)
        if with_err:
            err_ref[...] = jnp.zeros_like(err_ref)

    ok = v_ref[lane] > 0

    @pl.when(ok)
    def _compute():
        gt = w_ref[lane] * _tile(g_ref) + _tile(e_ref)
        keep = jnp.abs(gt) >= tau_ref[lane]
        if has_mask:
            keep = keep | (_tile(m_ref) > 0)
        gbar = jnp.where(keep, gt, 0.0)
        e_new = gt - gbar
        _store(gbar_ref, gbar)
        _store(enew_ref, e_new)
        nnz_ref[0] += _count(gbar != 0)
        if with_err:
            err_ref[0] += _pinned_tile_err(e_new * e_new)

    @pl.when(jnp.logical_not(ok))
    def _skip():
        gbar_ref[...] = jnp.zeros_like(gbar_ref)
        enew_ref[...] = jnp.zeros_like(enew_ref)


@functools.partial(jax.jit, static_argnames=("with_err", "interpret"))
def sparsify_ef_level_pallas(g, e, mask_in, weight, tau, valid, *,
                             with_err: bool = False,
                             interpret: bool = False):
    """Batched fused EF+sparsify. g,e: [W,d]; weight,tau,valid: [W];
    mask_in (optional [W,d]): keep mask OR-ed with the τ test (None skips
    the mask stream entirely — the pure-threshold sparsifier path).

    Returns (ḡ [W,d] g.dtype, e' [W,d] e.dtype, nnz [W] int32); with
    ``with_err``, appends the in-kernel pinned-order ‖e'‖² ([W] f32, see
    :func:`_pinned_tile_err`) — no separate jnp pass over e'.
    """
    w_lanes, d = g.shape
    n_blocks, pad = _geometry(d)
    gp = _pad_lanes(g.astype(jnp.float32), n_blocks, pad)
    ep = _pad_lanes(e.astype(jnp.float32), n_blocks, pad)
    has_mask = mask_in is not None
    operands = [gp, ep, weight.astype(jnp.float32), tau.astype(jnp.float32),
                valid.astype(jnp.float32)]
    in_specs = [_blk(), _blk(), _lane(), _lane(), _lane()]
    if has_mask:
        operands.append(_pad_lanes(mask_in.astype(jnp.float32), n_blocks,
                                   pad))
        in_specs.append(_blk())
    out_specs = [_blk(), _blk(), _acc()]
    out_shape = [
        jax.ShapeDtypeStruct(gp.shape, g.dtype),
        jax.ShapeDtypeStruct(ep.shape, e.dtype),
        _acc_shape(w_lanes),
    ]
    if with_err:
        out_specs.append(_acc())
        out_shape.append(_acc_shape(w_lanes, dtype=jnp.float32))

    out = pl.pallas_call(
        functools.partial(_sparsify_ef_level_kernel, has_mask=has_mask,
                          with_err=with_err),
        grid=(w_lanes, n_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    gbar, e_new, nnz = out[:3]
    res = (_unpad(gbar, w_lanes, d), _unpad(e_new, w_lanes, d),
           _counts(nnz))
    return res + (_lane_err(out[3]),) if with_err else res


# ---------------------------------------------------------------------------
# chain_accum_level — Algs 1/2/4 IA combine, fused support counts
# ---------------------------------------------------------------------------

def _chain_accum_level_kernel(gin_ref, gbar_ref, v_ref, *rest,
                              has_gmask: bool):
    if has_gmask:
        gm_ref, gout_ref, nnz_ref, off_ref = rest
    else:
        gout_ref, nnz_ref, off_ref = rest
    lane, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)
        off_ref[...] = jnp.zeros_like(off_ref)

    ok = v_ref[lane] > 0

    @pl.when(ok)
    def _compute():
        gamma = _tile(gin_ref) + _tile(gbar_ref)
        _store(gout_ref, gamma)
        nz = gamma != 0
        nnz_ref[0] += _count(nz)
        if has_gmask:
            off_ref[0] += _count(nz & (_tile(gm_ref) <= 0))
        else:
            off_ref[0] += _count(nz)

    @pl.when(jnp.logical_not(ok))
    def _skip():
        gout_ref[...] = jnp.zeros_like(gout_ref)


@functools.partial(jax.jit, static_argnames=("gmask_cohorts", "interpret"))
def chain_accum_level_pallas(gamma_in, gbar, valid, gmask=None, *,
                             gmask_cohorts: int = 0,
                             interpret: bool = False):
    """Batched γ_out = γ_in + ḡ with fused counts.

    gamma_in, gbar: [W,d]; valid: [W]; gmask (optional): the TCS global
    mask — per-lane [W,d], lane-shared [d] (streamed once per block, not
    broadcast), or cohort-shared [B,d] with ``gmask_cohorts=B`` (lanes
    flattened cohort-major); when given, ``nnz_off`` counts the off-mask
    support ``#{γ_out ≠ 0 ∧ m = 0}`` (the §V locally-indexed part);
    without it, ``nnz_off == nnz``.
    Returns (γ_out [W,d], nnz [W] i32, nnz_off [W] i32).
    """
    w_lanes, d = gamma_in.shape
    n_blocks, pad = _geometry(d)
    gi = _pad_lanes(gamma_in.astype(jnp.float32), n_blocks, pad)
    gb = _pad_lanes(gbar.astype(jnp.float32), n_blocks, pad)
    has_gmask = gmask is not None
    operands = [gi, gb, valid.astype(jnp.float32)]
    in_specs = [_blk(), _blk(), _lane()]
    if has_gmask:
        op, spec = _gmask_operand(gmask, w_lanes, gmask_cohorts, n_blocks,
                                  pad)
        operands.append(op)
        in_specs.append(spec)

    gout, nnz, nnz_off = pl.pallas_call(
        functools.partial(_chain_accum_level_kernel, has_gmask=has_gmask),
        grid=(w_lanes, n_blocks),
        in_specs=in_specs,
        out_specs=[_blk(), _acc(), _acc()],
        out_shape=[
            jax.ShapeDtypeStruct(gi.shape, gamma_in.dtype),
            _acc_shape(w_lanes),
            _acc_shape(w_lanes),
        ],
        interpret=interpret,
    )(*operands)
    return _unpad(gout, w_lanes, d), _counts(nnz), _counts(nnz_off)


# ---------------------------------------------------------------------------
# cl_fuse_level — Algs 3/5 complete node step in one pass
# ---------------------------------------------------------------------------

def _cl_fuse_level_kernel(g_ref, e_ref, gin_ref, w_ref, tau_ref, p_ref,
                          v_ref, *rest, has_gmask: bool, has_mask: bool,
                          with_err: bool):
    idx = 0
    gm_ref = mask_ref = err_ref = None
    if has_gmask:
        gm_ref = rest[idx]
        idx += 1
    if has_mask:
        mask_ref = rest[idx]
        idx += 1
    if with_err:
        gout_ref, enew_ref, nnz_ref, off_ref, err_ref = rest[idx:]
    else:
        gout_ref, enew_ref, nnz_ref, off_ref = rest[idx:]
    lane, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)
        off_ref[...] = jnp.zeros_like(off_ref)
        if with_err:
            err_ref[...] = jnp.zeros_like(err_ref)

    ok = v_ref[lane] > 0

    @pl.when(ok)
    def _compute():
        p = p_ref[lane]
        gt = w_ref[lane] * _tile(g_ref) + _tile(e_ref)
        gin = _tile(gin_ref)
        s = p * gt + gin
        if has_gmask:
            m = _tile(gm_ref)
            lam_t = (1.0 - m) * s
        else:
            lam_t = s
        keep = jnp.abs(lam_t) >= tau_ref[lane]
        if has_mask:
            keep = keep | (_tile(mask_ref) > 0)
        lam = jnp.where(keep, lam_t, 0.0)
        e_new = lam_t - lam
        gamma = (m * s + lam) if has_gmask else lam
        alive = p > 0
        gamma = jnp.where(alive, gamma, gin)
        e_new = jnp.where(alive, e_new, gt)
        _store(gout_ref, gamma)
        _store(enew_ref, e_new)
        nz = gamma != 0
        nnz_ref[0] += _count(nz)
        if has_gmask:
            off_ref[0] += _count(nz & (m <= 0))
        else:
            off_ref[0] += _count(nz)
        if with_err:
            err_ref[0] += _pinned_tile_err(e_new * e_new)

    @pl.when(jnp.logical_not(ok))
    def _skip():
        gout_ref[...] = jnp.zeros_like(gout_ref)
        enew_ref[...] = jnp.zeros_like(enew_ref)


@functools.partial(jax.jit, static_argnames=("gmask_cohorts", "with_err",
                                             "interpret"))
def cl_fuse_level_pallas(g, e, gamma_in, weight, tau, participate, valid,
                         gmask=None, mask_in=None, *,
                         gmask_cohorts: int = 0, with_err: bool = False,
                         interpret: bool = False):
    """Batched complete CL node step (Algs 3/5, stragglers included).

    g, e, gamma_in: [W,d]; weight, tau, participate, valid: [W];
    gmask (optional): TCS global mask m (Alg 5; None = Alg 3) — per-lane
    [W,d], lane-shared [d] (streamed once per block, not broadcast), or
    cohort-shared [B,d] with ``gmask_cohorts=B`` (lanes cohort-major);
    mask_in (optional, [W,d]): precomputed keep mask OR-ed with the τ test
    (pass τ=+inf for a pure-mask exact sparsifier).

    Returns (γ_out [W,d], e' [W,d], nnz [W] i32, nnz_off [W] i32) where
    ``nnz_off`` is the off-global-mask support (= nnz when gmask is None);
    with ``with_err``, appends the in-kernel pinned-order ‖e'‖² ([W] f32,
    see :func:`_pinned_tile_err`).
    """
    w_lanes, d = g.shape
    n_blocks, pad = _geometry(d)
    gp = _pad_lanes(g.astype(jnp.float32), n_blocks, pad)
    ep = _pad_lanes(e.astype(jnp.float32), n_blocks, pad)
    gi = _pad_lanes(gamma_in.astype(jnp.float32), n_blocks, pad)
    has_gmask = gmask is not None
    has_mask = mask_in is not None
    operands = [gp, ep, gi, weight.astype(jnp.float32),
                tau.astype(jnp.float32), participate.astype(jnp.float32),
                valid.astype(jnp.float32)]
    in_specs = [_blk(), _blk(), _blk(), _lane(), _lane(), _lane(), _lane()]
    if has_gmask:
        op, spec = _gmask_operand(gmask, w_lanes, gmask_cohorts, n_blocks,
                                  pad)
        operands.append(op)
        in_specs.append(spec)
    if has_mask:
        operands.append(_pad_lanes(mask_in.astype(jnp.float32), n_blocks,
                                   pad))
        in_specs.append(_blk())
    out_specs = [_blk(), _blk(), _acc(), _acc()]
    out_shape = [
        jax.ShapeDtypeStruct(gi.shape, gamma_in.dtype),
        jax.ShapeDtypeStruct(ep.shape, e.dtype),
        _acc_shape(w_lanes),
        _acc_shape(w_lanes),
    ]
    if with_err:
        out_specs.append(_acc())
        out_shape.append(_acc_shape(w_lanes, dtype=jnp.float32))

    out = pl.pallas_call(
        functools.partial(_cl_fuse_level_kernel, has_gmask=has_gmask,
                          has_mask=has_mask, with_err=with_err),
        grid=(w_lanes, n_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    gout, e_new, nnz, nnz_off = out[:4]
    res = (_unpad(gout, w_lanes, d), _unpad(e_new, w_lanes, d),
           _counts(nnz), _counts(nnz_off))
    return res + (_lane_err(out[4]),) if with_err else res


# ---------------------------------------------------------------------------
# count_ge_level — per-lane candidate-threshold counting (batched bisection)
# ---------------------------------------------------------------------------

def _count_candidates(out_ref, mag, taus_ref, lane, branch: int):
    """out_ref[0, b] += #{mag >= taus[lane, b]} (folded per tile)."""

    def body(b, _):
        out_ref[0, b] += _count(mag >= taus_ref[lane, b])
        return ()

    jax.lax.fori_loop(0, branch, body, ())


def _count_ge_level_kernel(x_ref, taus_ref, out_ref, *, branch: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _count_candidates(out_ref, jnp.abs(_tile(x_ref)), taus_ref,
                      pl.program_id(0), branch)


@functools.partial(jax.jit, static_argnames=("interpret",))
def count_ge_level_pallas(x: jax.Array, taus: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """counts[w, b] = #{i : |x_{w,i}| >= taus_{w,b}}; x [W,d], taus [W,B].

    Per-lane brackets of the batched branch-and-bisect Top-Q threshold
    search. The zero padding's contribution is subtracted in the wrapper
    (:func:`_drop_pad_level`) — exact for any taus, including
    non-positive ones.
    """
    w_lanes, d = x.shape
    branch = taus.shape[-1]
    n_blocks, pad = _geometry(d)
    xp = _pad_lanes(x.astype(jnp.float32), n_blocks, pad)
    taus = taus.astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_count_ge_level_kernel, branch=branch),
        grid=(w_lanes, n_blocks),
        in_specs=[_blk(), _lane()],
        out_specs=_acc(branch),
        out_shape=_acc_shape(w_lanes, branch),
        interpret=interpret,
    )(xp, taus)
    return _drop_pad_level(_counts(out), taus, pad)


# ---------------------------------------------------------------------------
# count_ge_fused_level — operand-on-the-fly candidate counting per lane
# ---------------------------------------------------------------------------

def _fused_operand_tile(g_ref, e_ref, gin_ref, gm_ref, w_ref, p_ref, lane, *,
                        include_gamma: bool, has_gmask: bool):
    """Reconstruct one (8, LANES) tile of the bisection operand in VMEM.

    Same float expression per element as the cl_fuse/sparsify_ef kernels
    (and the materialized jnp path): ``(1−m)·(p·(w·g + e) + γ_in)`` with
    the γ/mask factors dropped per the static flags.
    """
    op = w_ref[lane] * _tile(g_ref) + _tile(e_ref)
    if include_gamma:
        op = p_ref[lane] * op + _tile(gin_ref)
    if has_gmask:
        op = (1.0 - _tile(gm_ref)) * op
    return op


def _fused_refs(rest, include_gamma: bool, has_gmask: bool):
    """Split ``(γ_in?, w, p, m?, *outs)`` of the fused-operand kernels."""
    idx = 0
    gin_ref = gm_ref = None
    if include_gamma:
        gin_ref = rest[idx]
        idx += 1
    w_ref, p_ref = rest[idx:idx + 2]
    idx += 2
    if has_gmask:
        gm_ref = rest[idx]
        idx += 1
    return gin_ref, w_ref, p_ref, gm_ref, rest[idx:]


def _fused_operands(g, e, gamma_in, weight, participate, gmask, *,
                    include_gamma: bool, gmask_cohorts: int):
    """Operands and block specs shared by the fused-operand kernels."""
    w_lanes, d = g.shape
    n_blocks, pad = _geometry(d)
    operands = [_pad_lanes(g.astype(jnp.float32), n_blocks, pad),
                _pad_lanes(e.astype(jnp.float32), n_blocks, pad)]
    in_specs = [_blk(), _blk()]
    if include_gamma:
        operands.append(_pad_lanes(gamma_in.astype(jnp.float32), n_blocks,
                                   pad))
        in_specs.append(_blk())
    operands += [weight.astype(jnp.float32),
                 participate.astype(jnp.float32)]
    in_specs += [_lane(), _lane()]
    if gmask is not None:
        op, spec = _gmask_operand(gmask, w_lanes, gmask_cohorts, n_blocks,
                                  pad)
        operands.append(op)
        in_specs.append(spec)
    return operands, in_specs, (w_lanes, n_blocks), pad


def _count_ge_fused_level_kernel(g_ref, e_ref, *rest, branch: int,
                                 include_gamma: bool, has_gmask: bool):
    gin_ref, w_ref, p_ref, gm_ref, (taus_ref, out_ref) = _fused_refs(
        rest, include_gamma, has_gmask)
    lane = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    mag = jnp.abs(_fused_operand_tile(
        g_ref, e_ref, gin_ref, gm_ref, w_ref, p_ref, lane,
        include_gamma=include_gamma, has_gmask=has_gmask))
    _count_candidates(out_ref, mag, taus_ref, lane, branch)


@functools.partial(jax.jit, static_argnames=("include_gamma",
                                             "gmask_cohorts", "interpret"))
def count_ge_fused_level_pallas(g, e, gamma_in, weight, participate, taus,
                                gmask=None, *, include_gamma: bool = False,
                                gmask_cohorts: int = 0,
                                interpret: bool = False) -> jax.Array:
    """Per-lane candidate counts of the fused bisection operand.

    The τ-search operand (see :func:`_fused_operand_tile`) is rebuilt
    tile-by-tile from the raw node inputs — the materialized-g̃ HBM
    round-trip before ``threshold_for_topq`` disappears. g, e[, γ_in]:
    [W, d]; weight, participate: [W]; taus: [W, B]; gmask per
    :func:`_gmask_operand`. Returns counts [W, B] i32; zero padding
    reconstructs to exactly 0.0 and is subtracted in the wrapper.
    """
    branch = taus.shape[-1]
    taus = taus.astype(jnp.float32)
    operands, in_specs, grid, pad = _fused_operands(
        g, e, gamma_in, weight, participate, gmask,
        include_gamma=include_gamma, gmask_cohorts=gmask_cohorts)

    out = pl.pallas_call(
        functools.partial(_count_ge_fused_level_kernel, branch=branch,
                          include_gamma=include_gamma,
                          has_gmask=gmask is not None),
        grid=grid,
        in_specs=in_specs + [_lane()],
        out_specs=_acc(branch),
        out_shape=_acc_shape(grid[0], branch),
        interpret=interpret,
    )(*operands, taus)
    return _drop_pad_level(_counts(out), taus, pad)


# ---------------------------------------------------------------------------
# hist_topq_level — one-pass joint digit histogram (tau_impl="hist")
# ---------------------------------------------------------------------------

def _hist_topq_level_kernel(g_ref, e_ref, *rest, branch: int,
                            include_gamma: bool, has_gmask: bool):
    gin_ref, w_ref, p_ref, gm_ref, outs = _fused_refs(
        rest, include_gamma, has_gmask)
    tau1_ref, nl_ref, w2_ref, ts_ref, hist_ref = outs
    lane = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    mag = jnp.abs(_fused_operand_tile(
        g_ref, e_ref, gin_ref, gm_ref, w_ref, p_ref, lane,
        include_gamma=include_gamma, has_gmask=has_gmask))

    # round-1 digit: #{candidates <= |x|} — one vectorized compare per
    # candidate (the whole search's streaming passes collapse to this one)
    def cnt1(b, acc):
        return acc + (mag >= tau1_ref[lane, b]).astype(jnp.int32)

    d1 = jax.lax.fori_loop(0, branch, cnt1, jnp.zeros_like(mag, jnp.int32))

    # per-element bracket tables via one-hot select-sums (gathers are
    # hostile to the VPU; d1 is exact so exactly one term fires)
    def gather(bb, carry):
        nl, w2e, te = carry
        sel = d1 == bb
        nl = nl + jnp.where(sel, nl_ref[lane, bb], 0.0)
        w2e = w2e + jnp.where(sel, w2_ref[lane, bb], 0.0)
        te = te + jnp.where(sel, ts_ref[lane, bb], 0.0)
        return nl, w2e, te

    zeros = jnp.zeros_like(mag)
    nl, w2e, te = jax.lax.fori_loop(0, branch + 1, gather,
                                    (zeros, zeros, zeros))

    # round-2 digit within the element's own bracket — same candidate
    # expression fl(nl + fl(w2·j)) as the scan's second round
    def cnt2(b, acc):
        cand = nl + w2e * (b + 1).astype(jnp.float32)
        return acc + (mag >= cand).astype(jnp.int32)

    d2 = jax.lax.fori_loop(0, branch, cnt2, jnp.zeros_like(mag, jnp.int32))
    flag = mag >= te

    # joint histogram via one-hot contraction on the MXU, one tile row at a
    # time: H[r, c] = Σ_i 1[d1_i = r]·1[d2_i = c], with the last column
    # counting the flag per d1 bin. 0/1 operands and ≤ LANES-term sums are
    # exact in bf16 × bf16 → f32.
    bins = hist_ref.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bins, LANES), 0)
    for s in range(SUBLANES):
        oh1 = (iota == d1[s:s + 1]).astype(jnp.bfloat16)
        oh2 = ((iota == d2[s:s + 1])
               | ((iota == bins - 1) & flag[s:s + 1])).astype(jnp.bfloat16)
        hist_ref[0] += jax.lax.dot_general(
            oh1, oh2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("include_gamma",
                                             "gmask_cohorts", "interpret"))
def hist_topq_level_pallas(g, e, gamma_in, weight, participate, tables,
                           gmask=None, *, include_gamma: bool = False,
                           gmask_cohorts: int = 0,
                           interpret: bool = False):
    """One-pass joint digit histogram of the fused bisection operand.

    Collapses the `hist_rounds` sequential streaming passes of the
    branch-and-bisect scan into a single sweep: each element is binned by
    its round-1 digit d1 (which of the branch+1 round-1 brackets it falls
    in) and its round-2 digit d2 (candidate count within its *own*
    bracket), plus an exact flag for the bracket-top candidate. The bin
    edges are the scan's own bracket arithmetic
    (``repro.core.sparsify._hist_tables``), so the reconstructed per-round
    candidate counts are bit-identical integers to the scan
    (``_hist_bisect`` — branch=64, rounds=2 ⇒ 64² final resolution).

    ``tables = (tau1 [W,b], new_lo [W,b+1], w2 [W,b+1], top_shift [W,b+1])``;
    returns ``(D2 [W, b+1, b+1] i32, F [W, b+1] i32)``. Zero padding
    reconstructs to operand 0.0 → bin D2[·, 0, 0], which the
    reconstruction never reads (all candidates are strictly positive).
    """
    tau1 = tables[0]
    branch = tau1.shape[-1]
    bins = -(-(branch + 2) // VREG) * VREG      # digits 0..b + the flag
    operands, in_specs, grid, _ = _fused_operands(
        g, e, gamma_in, weight, participate, gmask,
        include_gamma=include_gamma, gmask_cohorts=gmask_cohorts)

    hist = pl.pallas_call(
        functools.partial(_hist_topq_level_kernel, branch=branch,
                          include_gamma=include_gamma,
                          has_gmask=gmask is not None),
        grid=grid,
        in_specs=in_specs + [_lane()] * 4,
        out_specs=pl.BlockSpec((1, bins, bins), lambda w, j: (w, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], bins, bins), jnp.int32),
        interpret=interpret,
    )(*operands, *(t.astype(jnp.float32) for t in tables))
    with jax.named_scope(scopes.KERNEL_LAYOUT):
        return (hist[:, :branch + 1, :branch + 1],
                hist[:, :branch + 1, bins - 1])
