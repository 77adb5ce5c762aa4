"""The paper's five sparse incremental-aggregation algorithms.

Each algorithm is a *node step*: what client k does with its own effective
gradient ``g_k`` and the incoming partial aggregate ``γ_{k+1}`` before
forwarding ``γ_k`` toward the parameter server.

All five are implemented over **dense d-vectors** (the sparse structure is in
the zero pattern) with *bit-exact* communication accounting per §V — this is
the semantics layer used by the simulator, the tests, and (per-shard) by the
distributed ring. Static-shape compact transport lives in ``ring.py``.

Two execution forms share the semantics: the scalar :func:`node_step`
(one node, one d-vector — the chain scan, the ring's register loop, the
client-per-rank device kernel) and the batched :func:`level_step` (all W
slots of a padded schedule level at once — the plan executors). Both
dispatch their sparsify+EF and IA-combine stages through the Pallas
kernels of :mod:`repro.kernels` when ``AggConfig.kernel_mode`` resolves to
them (TPU, or interpret mode under ``REPRO_PALLAS_INTERPRET=1``);
otherwise the unfused jnp bodies below run unchanged and remain the
bit-exact oracle.

Naming (paper §VI): Alg1=SIA, Alg2=RE-SIA, Alg3=CL-SIA, Alg4=TC-SIA,
Alg5=CL-TC-SIA.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import sparsify as sp
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import scopes

Array = jax.Array


class AggKind(str, enum.Enum):
    SIA = "sia"                # Alg 1 (SoA baseline, [1])
    RE_SIA = "re_sia"          # Alg 2
    CL_SIA = "cl_sia"          # Alg 3
    TC_SIA = "tc_sia"          # Alg 4
    CL_TC_SIA = "cl_tc_sia"    # Alg 5
    DENSE_IA = "dense_ia"      # IA without sparsification (upper baseline)
    ROUTING = "routing"        # conventional routing (no IA; cost model only)


@dataclasses.dataclass(frozen=True)
class AggConfig:
    """Static configuration of a sparse-IA aggregator.

    ``q`` is the per-hop budget. For time-correlated variants, ``q_global``
    and ``q_local`` split it (paper: Q_L = 0.1·Q, Q_G = Q − Q_L).
    ``omega`` is the payload word size in bits (ω); index cost is
    ⌈log₂ d⌉ bits per locally-indexed nonzero.
    """

    kind: AggKind = AggKind.CL_SIA
    q: int = 78
    q_global: int = 0
    q_local: int = 0
    omega: int = 32
    # Top-Q implementation: "exact" (lax.top_k oracle) or "threshold"
    # (branch-and-bisect counting; distributable, kernel-accelerated).
    topq_impl: str = "exact"
    hist_branch: int = 64
    hist_rounds: int = 3
    # τ-search implementation for the threshold sparsifier: "scan" (the
    # streaming multi-pass branch-and-bisect) or "hist" (one joint digit
    # histogram replaces the hist_rounds sequential passes; requires
    # hist_rounds ∈ {1, 2} — per-round candidate counts and τ stay
    # bit-identical to the scan, see sparsify._hist_bisect).
    tau_impl: str = "scan"
    # ‖e'‖² reduction: "jnp" (the historic vmapped row-sum — HopStats stay
    # bit-comparable with the unfused bodies) or "kernel" (in-kernel
    # pinned pairwise-tree order, see kernels.level._pinned_tile_err — no
    # separate jnp pass over e', but a *different* documented summation
    # order).
    err_sq_mode: str = "jnp"
    # Wire dtype for compact ring transport values (f32 matches ω=32;
    # bfloat16 is the beyond-paper ω=16 quantization knob).
    wire_dtype: str = "float32"
    # Fused-kernel dispatch for the node-step hot path (repro.kernels):
    # "auto" = compiled Pallas on TPU, Pallas-interpret off-TPU only when
    # REPRO_PALLAS_INTERPRET=1, pure-jnp otherwise (the host executors stay
    # the bit-exact oracle); "always" = force the kernels (interpret mode
    # off-TPU — parity tests); "never" = force the unfused jnp reference;
    # "ref" = fused structure (whole-level steps, fused-operand τ search)
    # with the jnp reference kernel bodies — the honest host benchmark of
    # the fused data flow.
    kernel_mode: str = "auto"

    def __post_init__(self):
        if self.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
            if self.q_global <= 0 and self.q_local <= 0 and self.q > 0:
                # paper's default split
                ql = max(1, round(0.1 * self.q))
                object.__setattr__(self, "q_local", ql)
                object.__setattr__(self, "q_global", self.q - ql)
        # q == 0 is a degenerate-but-valid budget (nothing transmitted,
        # everything banks into EF) — it arises when a global budget is
        # split over more ring segments than it has coordinates
        # (core.ring.segment_budget clamps rather than inflate §V bits).
        # Warn loudly: a hand-built q=0 config trains a flat loss curve.
        if self.kernel_mode not in ("auto", "always", "never", "ref"):
            raise ValueError(f"unknown kernel_mode {self.kernel_mode!r} "
                             f"(expected 'auto', 'always', 'never' or "
                             f"'ref')")
        if self.tau_impl not in ("scan", "hist"):
            raise ValueError(f"unknown tau_impl {self.tau_impl!r} "
                             f"(expected 'scan' or 'hist')")
        if self.tau_impl == "hist" and self.hist_rounds not in (1, 2):
            raise ValueError(
                "tau_impl='hist' folds the whole τ search into one "
                f"histogram pass; hist_rounds must be 1 or 2, got "
                f"{self.hist_rounds}")
        if self.err_sq_mode not in ("jnp", "kernel"):
            raise ValueError(f"unknown err_sq_mode {self.err_sq_mode!r} "
                             f"(expected 'jnp' or 'kernel')")
        if self.kind not in (AggKind.DENSE_IA, AggKind.ROUTING):
            if self.q < 0:
                raise ValueError("q must be non-negative for sparsified "
                                 "aggregation")
            if self.q == 0:
                import warnings
                warnings.warn(
                    "AggConfig q=0: nothing will be transmitted and the "
                    "model will not update (valid only as the clamped "
                    "too-small-global-budget edge case)", stacklevel=2)

    def topq_fn(self) -> Callable[[Array, int], Array]:
        if self.topq_impl == "exact":
            return sp.topq
        if self.topq_impl == "threshold":
            return lambda x, q: sp.topq_by_threshold(
                x, q, branch=self.hist_branch, rounds=self.hist_rounds,
                tau_impl=self.tau_impl)
        raise ValueError(f"unknown topq_impl {self.topq_impl!r}")

    def topq_mask_fn(self) -> Callable[[Array, int], Array]:
        if self.topq_impl == "exact":
            return sp.topq_mask
        if self.topq_impl == "threshold":
            def mask(x, q):
                tau = sp.threshold_for_topq(
                    x, q, branch=self.hist_branch, rounds=self.hist_rounds,
                    tau_impl=self.tau_impl)
                return (jnp.abs(x) >= tau).astype(x.dtype)
            return mask
        raise ValueError(f"unknown topq_impl {self.topq_impl!r}")


class HopStats(NamedTuple):
    """Per-hop accounting (all traced scalars).

    ``bits`` follows §V exactly: globally-masked values cost ω each (indices
    implicit), locally-indexed nonzeros cost ω + ⌈log₂ d⌉ each.
    """

    nnz_out: Array          # ‖γ_k‖₀ transmitted by this hop
    nnz_global: Array       # ‖Γ_k‖₀ part (0 for non-TC algorithms)
    nnz_local: Array        # ‖Λ_k‖₀ part (= nnz_out for non-TC)
    bits: Array             # exact transmitted bits for this hop
    err_sq: Array           # ‖e_k^t‖² sparsification error after this hop


class NodeCtx(NamedTuple):
    """Round-constant context shared by all hops.

    ``global_mask`` is the TCS mask m^t = s(w^t − w^{t−1}, Q_G) (zeros for
    non-TC algorithms). ``participate`` ∈ {0.,1.}: straggler/failure mask —
    a non-participating node forwards γ unchanged and banks its entire g̃
    into error feedback (see DESIGN §6). ``q_budget`` (optional, traced
    int32) overrides the node's *local* Top-Q budget (``q`` / ``q_local``) —
    the bandwidth-aware path where narrow uplinks get smaller budgets; None
    keeps the static-``q`` exact Top-Q, bit-identical to the paper setting.
    """

    global_mask: Array
    participate: Array
    q_budget: Optional[Array] = None


def index_bits(d: int) -> int:
    """⌈log₂ d⌉ — bits to address one coordinate of a length-d vector."""
    import math
    return max(1, math.ceil(math.log2(d)))


def _bits(cfg: AggConfig, d: int, nnz_global: Array, nnz_local: Array) -> Array:
    # float32: bit counts for billion-parameter models overflow int32; the
    # ~2^-24 relative rounding is irrelevant for accounting.
    ib = index_bits(d)
    return (cfg.omega * nnz_global.astype(jnp.float32)
            + (cfg.omega + ib) * nnz_local.astype(jnp.float32))


def _topq_local(cfg: AggConfig, ctx: NodeCtx, x: Array, q: int) -> Array:
    """Local Top-Q values under the node's effective budget."""
    if ctx.q_budget is None:
        return cfg.topq_fn()(x, q)
    return sp.topq_dynamic(x, ctx.q_budget)


def _topq_mask_local(cfg: AggConfig, ctx: NodeCtx, x: Array, q: int) -> Array:
    """Local Top-Q mask under the node's effective budget."""
    if ctx.q_budget is None:
        return cfg.topq_mask_fn()(x, q)
    return sp.topq_mask_dynamic(x, ctx.q_budget)


# ---------------------------------------------------------------------------
# Fused whole-level node steps (the repro.kernels hot path)
#
# Each `_fused_level_*` runs one schedule level — up to W concurrent tree
# nodes, inputs [W, d] — through the batched Pallas kernels: the EF +
# sparsify and IA-combine stages stream HBM once per level instead of once
# per jnp op (per-algorithm sweep table: benchmarks/bench_round.py::
# vector_passes — e.g. CL-SIA 7 unfused → 5 fused). The
# dispatch is trace-time (`cfg.kernel_mode` × backend, see
# :func:`repro.kernels.ops.resolve`): off-TPU without
# REPRO_PALLAS_INTERPRET=1 the unfused jnp bodies below run unchanged, so
# the host executors remain the bit-exact oracle. In interpret mode the
# fused outputs are bit-exact to the unfused bodies under jit (both sides
# see XLA's FMA contraction of w·g+e; eager unfused differs by 1 ulp —
# tests/test_fused_node_step.py pins this).
#
# All five sparsified algorithms are covered. Per-lane sparsifier state
# (exact Top-Q masks, dynamic-budget sort masks, threshold-bisection τ) is
# computed through a TauOperand built from the raw node inputs
# (`_tau_operand`): the exact/dynamic paths materialize the operand (they
# need the full sort anyway), while the threshold path never does — its
# candidate counts stream through the fused-operand kernels
# (`count_ge_fused_level`, or one `hist_topq_level` pass under
# tau_impl="hist"), reconstructing |…·(w·g + e) + …| tile-by-tile in VMEM.
# ---------------------------------------------------------------------------

#: Bit counts, error-feedback rows, aggregates and nnz/bits stats of the
#: fused paths are bit-exact to the unfused bodies; err_sq defaults to the
#: same vmapped jnp reduction on both paths (err_sq_mode="jnp") to keep the
#: full HopStats comparable bitwise — err_sq_mode="kernel" swaps in the
#: in-kernel pinned pairwise-tree reduction (no extra pass over e', a
#: documented *different* summation order).

_FUSED_KINDS = (AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
                AggKind.CL_TC_SIA)


def fused_node_steps(cfg: AggConfig, *operands) -> bool:
    """True when ``cfg`` dispatches node steps through the fused level path.

    Trace-time decision: the algorithm has a fused form, the resolved
    backend uses Pallas (see :func:`repro.kernels.ops.resolve`) — or
    ``kernel_mode="ref"``, which keeps the fused *structure* with the jnp
    reference kernel bodies — and the promoted compute dtype is float32
    (the kernels compute in f32; an all-bf16 operand set would change
    rounding, so it falls back to the unfused jnp path).
    """
    if cfg.kind not in _FUSED_KINDS:
        return False
    if cfg.kernel_mode != "ref" and not kops.resolve(cfg.kernel_mode)[0]:
        return False
    return (not operands
            or jnp.result_type(*operands) == jnp.float32)


def _f32(x: Array) -> Array:
    return jnp.asarray(x, jnp.float32)


def _lane_inf(w: int) -> Array:
    return jnp.full((w,), jnp.inf, jnp.float32)


_tau_scope = jax.named_scope(scopes.TAU_SEARCH)
_node_scope = jax.named_scope(scopes.NODE_STEP)


def _tau_operand(cfg: AggConfig, g, e, gam, w, p, gm=None, cohorts=0, *,
                 include_gamma: bool = False) -> sp.TauOperand:
    """Build the level's bisection operand from the raw node inputs.

    The returned :class:`repro.core.sparsify.TauOperand` streams candidate
    counts (and the tau_impl="hist" digit histogram) through the
    fused-operand kernels — ``|…·(w·g + e) + …|`` is reconstructed
    tile-by-tile in VMEM, never materialized to HBM for the τ search.
    ``materialize()`` (the exact/dynamic sparsifier paths, which need the
    full sort anyway) and ``max_abs()`` use the identical float expression
    (:func:`repro.kernels.ref.fused_operand`), so every path stays bitwise
    interchangeable with the historic materialized-x search.
    """
    mode = cfg.kernel_mode

    @_tau_scope
    def materialize():
        return kref.fused_operand(g, e, gam, w, p, gm,
                                  include_gamma=include_gamma,
                                  gmask_cohorts=cohorts)

    @_tau_scope
    def count(taus):
        return kops.count_ge_fused_level(
            g, e, gam, w, p, taus, gm, include_gamma=include_gamma,
            gmask_cohorts=cohorts, mode=mode)

    @_tau_scope
    def max_abs():
        # XLA fuses the elementwise operand into the reduce — one streaming
        # pass, no [W, d] landing in HBM; bitwise equal to a materialized
        # jnp.max(jnp.abs(x)) (same expression, same reduction)
        mag = jnp.abs(materialize())
        if not mag.size:
            return jnp.zeros(mag.shape[:-1], jnp.float32)
        return jnp.max(mag, axis=-1)

    @_tau_scope
    def hist(tables):
        return kops.hist_topq_level(
            g, e, gam, w, p, tables, gm, include_gamma=include_gamma,
            gmask_cohorts=cohorts, mode=mode)

    return sp.TauOperand(count=count, max_abs=max_abs, batched=True,
                         hist=hist, materialize=materialize)


def _lane_sparsifier_state(cfg: AggConfig, operand: sp.TauOperand, q: int,
                           p: Array, qb: Optional[Array]):
    """Per-lane sparsifier state for a batched [W, d] bisection operand.

    Returns ``(mask_in, tau)`` such that ``keep = (|x| >= tau) | mask_in``
    reproduces the unfused ``_topq_local`` keep set lane by lane:

    * dynamic budgets → the sort-threshold keep mask, τ = +inf;
    * exact Top-Q     → the ``lax.top_k`` support mask (by the counting
      select at large d, :func:`repro.core.sparsify.topq_mask`), τ = +inf;
    * threshold Top-Q → mask None, τ from the batched branch-and-bisect
      over the *unmaterialized* operand (fused-operand count kernels; one
      histogram pass under ``cfg.tau_impl="hist"``).

    Non-participating lanes (p = 0) are zeroed out of mask/τ — the
    sparsify_ef stage then banks the whole g̃ into error feedback, exactly
    the unfused straggler algebra. (The CL kernels override stragglers
    internally, where this zeroing is a harmless no-op.)
    """
    w = p.shape[0]
    if qb is not None:
        mask = jax.vmap(sp.topq_mask_dynamic)(operand.materialize(), qb)
        return mask * p[:, None], _lane_inf(w)
    if cfg.topq_impl == "threshold":
        tau = sp.threshold_for_topq(
            None, q, branch=cfg.hist_branch, rounds=cfg.hist_rounds,
            operand_fn=operand, tau_impl=cfg.tau_impl)
        return None, jnp.where(p > 0, tau, jnp.inf)
    mask = sp.topq_mask(operand.materialize(), q)
    return mask * p[:, None], _lane_inf(w)


def _lane_err_sq(e_new: Array) -> Array:
    return jax.vmap(lambda v: jnp.sum(v.astype(jnp.float32) ** 2))(e_new)


def _stats_no_gmask(cfg: AggConfig, d: int, nnz: Array, e_new: Array,
                    err: Optional[Array] = None) -> HopStats:
    zeros = jnp.zeros_like(nnz)
    return HopStats(nnz_out=nnz, nnz_global=zeros, nnz_local=nnz,
                    bits=_bits(cfg, d, zeros, nnz),
                    err_sq=_lane_err_sq(e_new) if err is None else err)


def _stats_gmask(cfg: AggConfig, d: int, gm: Array, nnz: Array,
                 nnz_off: Array, e_new: Array, cohorts: int = 0,
                 err: Optional[Array] = None) -> HopStats:
    if gm.ndim == 1:       # lane-shared mask: one count, broadcast
        nz_g = jnp.broadcast_to(jnp.sum(gm > 0).astype(jnp.int32),
                                nnz.shape)
    elif cohorts and gm.shape[0] != nnz.shape[0]:
        # cohort-shared [B, d] mask over B*W cohort-major lanes: one count
        # per cohort, tiled to its W lanes — the same per-row reduction the
        # sequential lane-shared branch runs, so bitwise comparable
        nz_gc = jnp.sum(gm > 0, axis=-1).astype(jnp.int32)
        nz_g = jnp.repeat(nz_gc, nnz.shape[0] // cohorts)
    else:
        nz_g = jax.vmap(
            lambda m: jnp.sum(m > 0).astype(jnp.int32))(gm)
    return HopStats(nnz_out=nnz, nnz_global=nz_g, nnz_local=nnz_off,
                    bits=_bits(cfg, d, nz_g, nnz_off),
                    err_sq=_lane_err_sq(e_new) if err is None else err)


def _gm_rows(gm: Array, lanes: int, cohorts: int) -> Array:
    """Per-lane-broadcastable view of the gmask for jnp-side level math.

    A cohort-shared [B, d] mask (``cohorts=B``, lanes cohort-major) is
    expanded lazily to [lanes, d] — XLA fuses the equal-repeat broadcast,
    nothing lands in HBM; the kernels keep streaming the compact [B, d]
    form through their cohort-shared block spec.
    """
    if cohorts and gm.ndim == 2 and gm.shape[0] != lanes:
        return jnp.repeat(gm, lanes // cohorts, axis=0)
    return gm


def _fused_level_sia(cfg, g, gam, e, w, p, gm, qb, valid, cohorts=0):
    d = g.shape[-1]
    op = _tau_operand(cfg, g, e, None, w, p)
    mask, tau = _lane_sparsifier_state(cfg, op, cfg.q, p, qb)
    we = cfg.err_sq_mode == "kernel"
    out = kops.sparsify_ef_level(g, e, mask, w, tau, valid, with_err=we,
                                 mode=cfg.kernel_mode)
    gbar, e_new = out[0], out[1]
    gout, nnz, _ = kops.chain_accum_level(gam, gbar, valid,
                                          mode=cfg.kernel_mode)
    return gout, e_new, _stats_no_gmask(cfg, d, nnz, e_new,
                                        out[3] if we else None)


def _fused_level_re_sia(cfg, g, gam, e, w, p, gm, qb, valid, cohorts=0):
    d = g.shape[-1]
    op = _tau_operand(cfg, g, e, None, w, p)
    m_in = sp.support(gam)
    if qb is None and cfg.topq_impl == "threshold":
        _, tau = _lane_sparsifier_state(cfg, op, cfg.q, p, qb)
        mask = m_in * p[:, None]
    else:
        m_l, tau = _lane_sparsifier_state(cfg, op, cfg.q,
                                          jnp.ones_like(p), qb)
        mask = sp.mask_union(m_l, m_in) * p[:, None]
    we = cfg.err_sq_mode == "kernel"
    out = kops.sparsify_ef_level(g, e, mask, w, tau, valid, with_err=we,
                                 mode=cfg.kernel_mode)
    gbar, e_new = out[0], out[1]
    gout, nnz, _ = kops.chain_accum_level(gam, gbar, valid,
                                          mode=cfg.kernel_mode)
    return gout, e_new, _stats_no_gmask(cfg, d, nnz, e_new,
                                        out[3] if we else None)


def _fused_level_tc_sia(cfg, g, gam, e, w, p, gm, qb, valid, cohorts=0):
    d = g.shape[-1]
    gme = _gm_rows(gm, g.shape[0], cohorts)
    op = _tau_operand(cfg, g, e, None, w, p, gm, cohorts)
    m_k, tau = _lane_sparsifier_state(cfg, op, cfg.q_local,
                                      jnp.ones_like(p), qb)
    m_in = jnp.clip(sp.support(gam) - gme, 0, 1)
    if m_k is None:
        # threshold impl: materialize the local mask to union it with the
        # global/incoming masks (matches the unfused topq_mask_fn exactly)
        x = op.materialize()
        m_k = (jnp.abs(x) >= tau[:, None]).astype(x.dtype)
        tau = _lane_inf(g.shape[0])
    mm = sp.mask_union(gme, m_k, m_in)
    mask = mm * p[:, None]
    we = cfg.err_sq_mode == "kernel"
    out = kops.sparsify_ef_level(g, e, mask, w, tau, valid, with_err=we,
                                 mode=cfg.kernel_mode)
    gbar, e_new = out[0], out[1]
    gout, nnz, nnz_off = kops.chain_accum_level(gam, gbar, valid, gm,
                                                gmask_cohorts=cohorts,
                                                mode=cfg.kernel_mode)
    return gout, e_new, _stats_gmask(cfg, d, gm, nnz, nnz_off, e_new,
                                     cohorts, out[3] if we else None)


def _fused_level_cl_sia(cfg, g, gam, e, w, p, gm, qb, valid, cohorts=0):
    d = g.shape[-1]
    op = _tau_operand(cfg, g, e, gam, w, p, include_gamma=True)
    mask, tau = _lane_sparsifier_state(cfg, op, cfg.q, jnp.ones_like(p),
                                       qb)
    we = cfg.err_sq_mode == "kernel"
    out = kops.cl_fuse_level(g, e, gam, w, tau, p, valid, mask_in=mask,
                             with_err=we, mode=cfg.kernel_mode)
    gout, e_new, nnz = out[0], out[1], out[2]
    return gout, e_new, _stats_no_gmask(cfg, d, nnz, e_new,
                                        out[4] if we else None)


def _fused_level_cl_tc_sia(cfg, g, gam, e, w, p, gm, qb, valid, cohorts=0):
    d = g.shape[-1]
    op = _tau_operand(cfg, g, e, gam, w, p, gm, cohorts,
                      include_gamma=True)
    mask, tau = _lane_sparsifier_state(cfg, op, cfg.q_local,
                                       jnp.ones_like(p), qb)
    we = cfg.err_sq_mode == "kernel"
    out = kops.cl_fuse_level(
        g, e, gam, w, tau, p, valid, gmask=gm, mask_in=mask,
        gmask_cohorts=cohorts, with_err=we, mode=cfg.kernel_mode)
    gout, e_new, nnz, nnz_off = out[0], out[1], out[2], out[3]
    return gout, e_new, _stats_gmask(cfg, d, gm, nnz, nnz_off, e_new,
                                     cohorts, out[4] if we else None)


_FUSED_LEVEL = {
    AggKind.SIA: _fused_level_sia,
    AggKind.RE_SIA: _fused_level_re_sia,
    AggKind.CL_SIA: _fused_level_cl_sia,
    AggKind.TC_SIA: _fused_level_tc_sia,
    AggKind.CL_TC_SIA: _fused_level_cl_tc_sia,
}


@_node_scope
def _run_fused_level(cfg, g, gamma_in, e, weight, participate, global_mask,
                     q_budget, valid, cohorts=0):
    w_lanes = g.shape[0]
    # a 1-D (lane-shared) TCS mask stays 1-D all the way into the kernels:
    # the level kernels stream it once per block (shared block spec)
    # instead of materializing a [W, d] broadcast in HBM; a cohort-shared
    # [B, d] mask (``cohorts=B``, lanes cohort-major) likewise streams
    # through the cohort block spec
    gm = _f32(global_mask)
    qb = None if q_budget is None else jnp.asarray(q_budget, jnp.int32)
    v = (jnp.ones((w_lanes,), jnp.float32) if valid is None
         else _f32(valid))
    gout, e_new, stats = _FUSED_LEVEL[cfg.kind](
        cfg, _f32(g), _f32(gamma_in), _f32(e), _f32(weight),
        _f32(participate), gm, qb, v, cohorts)
    # padding lanes count nothing — the kernels already zero their outputs
    # and nnz accumulators, but the jnp-side global-mask word count
    # (nnz_global → bits) is lane-agnostic and must be masked to keep the
    # fused and unfused modes interchangeable (see the unfused branch of
    # level_step)
    ok = v > 0
    stats = jax.tree.map(lambda s: jnp.where(ok, s, jnp.zeros_like(s)),
                         stats)
    return gout, e_new, stats


def _fused_scalar(cfg: AggConfig, g, gamma_in, e, weight, ctx: NodeCtx):
    """Scalar-lane (d-vector) entry into the fused level path, or None.

    Used by the per-node consumers — the sequential chain, the ring's
    register fast path, the client-per-rank device kernel — which step one
    node at a time: the node becomes a W=1 level.
    """
    if getattr(g, "ndim", 1) != 1:
        return None
    if not fused_node_steps(cfg, weight, g, e, gamma_in):
        return None
    qb = (None if ctx.q_budget is None
          else jnp.asarray(ctx.q_budget, jnp.int32).reshape(1))
    gout, e_new, stats = _run_fused_level(
        cfg, g[None], gamma_in[None], e[None],
        jnp.asarray(weight, jnp.float32).reshape(1),
        jnp.asarray(ctx.participate, jnp.float32).reshape(1),
        _f32(ctx.global_mask), qb, None)
    stats = jax.tree.map(lambda s: s[0], stats)
    if cfg.err_sq_mode == "jnp":
        # scalar-form err reduction: a vmapped row-sum accumulates in a
        # different order than the unfused scalar `_finalize` sum (1 ulp) —
        # recompute it the scalar way so HopStats stay fully bit-comparable
        # (err_sq_mode="kernel" keeps the pinned in-kernel value instead:
        # its tile-tree order is already lane-layout invariant)
        stats = stats._replace(
            err_sq=jnp.sum(e_new[0].astype(jnp.float32) ** 2))
    return gout[0], e_new[0], stats


# ---------------------------------------------------------------------------
# Node steps. Signature:  (cfg, g, gamma_in, e, weight, ctx) ->
#                         (gamma_out, e_new, HopStats)
# ---------------------------------------------------------------------------

def _finalize(cfg: AggConfig, d: int, gamma_out: Array, e_new: Array,
              global_mask: Array) -> tuple[Array, Array, HopStats]:
    lam = gamma_out * (1 - global_mask)
    nz_l = sp.nnz(lam)
    # Γ part is transmitted densely in the Q_G known slots → costs Q_G words
    # whenever a global mask is active, regardless of zero values inside it.
    nz_g = jnp.sum(global_mask > 0).astype(jnp.int32)
    stats = HopStats(
        nnz_out=sp.nnz(gamma_out),
        nnz_global=nz_g,
        nnz_local=nz_l,
        bits=_bits(cfg, d, nz_g, nz_l),
        err_sq=jnp.sum(e_new.astype(jnp.float32) ** 2),
    )
    return gamma_out, e_new, stats


@_node_scope
def step_sia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
             weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """Alg 1 — SoA sparse IA: local Top-Q then add."""
    fused = _fused_scalar(cfg, g, gamma_in, e, weight, ctx)
    if fused is not None:
        return fused
    d = g.shape[-1]
    gt = weight * g + e                               # line 2
    gbar = _topq_local(cfg, ctx, gt, cfg.q)           # line 3
    gbar = gbar * ctx.participate
    e_new = gt - gbar                                 # line 4
    gamma_out = gbar + gamma_in                       # line 5
    return _finalize(cfg, d, gamma_out, e_new, jnp.zeros_like(g))


@_node_scope
def step_re_sia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
                weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """Alg 2 — reduced-error: transmit inside union(local Top-Q, incoming)."""
    fused = _fused_scalar(cfg, g, gamma_in, e, weight, ctx)
    if fused is not None:
        return fused
    d = g.shape[-1]
    gt = weight * g + e                               # line 2
    m_local = _topq_mask_local(cfg, ctx, gt, cfg.q)   # line 3
    m_in = sp.support(gamma_in)                       # line 4
    m = sp.mask_union(m_local, m_in)                  # line 5
    gbar = m * gt * ctx.participate
    e_new = gt - gbar                                 # line 6
    gamma_out = gbar + gamma_in                       # line 7
    return _finalize(cfg, d, gamma_out, e_new, jnp.zeros_like(g))


@_node_scope
def step_cl_sia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
                weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """Alg 3 — constant-length: aggregate then Top-Q. ‖γ_out‖₀ ≤ Q."""
    fused = _fused_scalar(cfg, g, gamma_in, e, weight, ctx)
    if fused is not None:
        return fused
    d = g.shape[-1]
    gt = weight * g + e                               # line 2
    gamma_tilde = ctx.participate * gt + gamma_in     # line 3
    gamma_out = _topq_local(cfg, ctx, gamma_tilde, cfg.q)   # line 4
    e_new = gamma_tilde - gamma_out                   # line 5
    # Straggler semantics (model (a), DESIGN §6): the node computed g but
    # missed the transmit deadline → γ forwarded unchanged, the *entire*
    # effective gradient g̃ banks into error feedback for later rounds.
    gamma_out = jnp.where(ctx.participate > 0, gamma_out, gamma_in)
    e_new = jnp.where(ctx.participate > 0, e_new, gt)
    return _finalize(cfg, d, gamma_out, e_new, jnp.zeros_like(g))


@_node_scope
def step_tc_sia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
                weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """Alg 4 — time-correlated sparse IA (global mask + Q_L local + incoming)."""
    fused = _fused_scalar(cfg, g, gamma_in, e, weight, ctx)
    if fused is not None:
        return fused
    d = g.shape[-1]
    m = ctx.global_mask                                # line 3 (precomputed)
    gt = weight * g + e                                # line 2
    m_k = _topq_mask_local(cfg, ctx, (1 - m) * gt, cfg.q_local)   # line 4
    m_in = jnp.clip(sp.support(gamma_in) - m, 0, 1)    # line 5
    mm = sp.mask_union(m, m_k, m_in)                   # line 6
    gbar = mm * gt * ctx.participate
    e_new = gt - gbar                                  # line 7
    gamma_out = gamma_in + gbar                        # line 8 / eq (6)
    return _finalize(cfg, d, gamma_out, e_new, m)


@_node_scope
def step_cl_tc_sia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
                   weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """Alg 5 — constant-length time-correlated: CL-SIA on the off-mask part.

    Γ is aggregated densely inside the global mask (cost ω·Q_G, no indices);
    the off-mask part is CL-sparsified to Q_L. See DESIGN §1 for the printed
    listing's line-5 typo and the reading used here.
    """
    fused = _fused_scalar(cfg, g, gamma_in, e, weight, ctx)
    if fused is not None:
        return fused
    d = g.shape[-1]
    m = ctx.global_mask                                # line 3
    gt = weight * g + e                                # line 2
    contrib = ctx.participate * gt
    gamma_g = m * (gamma_in + contrib)                 # line 4: Γ_k
    lam_tilde = (1 - m) * (gamma_in + contrib)         # line 5: Λ̃_k
    lam = _topq_local(cfg, ctx, lam_tilde, cfg.q_local)  # line 5: Λ_k = S(Λ̃,Q_L)
    e_new = lam_tilde - lam                            # line 6
    gamma_out = gamma_g + lam
    gamma_out = jnp.where(ctx.participate > 0, gamma_out, gamma_in)
    e_new = jnp.where(ctx.participate > 0, e_new, gt)
    return _finalize(cfg, d, gamma_out, e_new, m)


@_node_scope
def step_dense_ia(cfg: AggConfig, g: Array, gamma_in: Array, e: Array,
                  weight: Array, ctx: NodeCtx) -> tuple[Array, Array, HopStats]:
    """IA without sparsification — the efficiency upper baseline (Fig 2b)."""
    d = g.shape[-1]
    gt = weight * g + e
    gamma_out = gamma_in + ctx.participate * gt
    e_new = jnp.where(ctx.participate > 0, jnp.zeros_like(e), gt)
    # dense transmission: d words, no index overhead
    bits = jnp.asarray(cfg.omega * d, jnp.float32)
    stats = HopStats(nnz_out=jnp.asarray(d, jnp.int32),
                     nnz_global=jnp.asarray(d, jnp.int32),
                     nnz_local=jnp.asarray(0, jnp.int32),
                     bits=bits,
                     err_sq=jnp.sum(e_new.astype(jnp.float32) ** 2))
    return gamma_out, e_new, stats


NODE_STEPS = {
    AggKind.SIA: step_sia,
    AggKind.RE_SIA: step_re_sia,
    AggKind.CL_SIA: step_cl_sia,
    AggKind.TC_SIA: step_tc_sia,
    AggKind.CL_TC_SIA: step_cl_tc_sia,
    AggKind.DENSE_IA: step_dense_ia,
}


def node_step(cfg: AggConfig):
    """Return the node-step function for ``cfg.kind``."""
    if cfg.kind == AggKind.ROUTING:
        raise ValueError(
            "ROUTING has no node step: it is a cost model (every client's "
            "sparse gradient is forwarded unmodified through all hops); use "
            "comm_cost.routing_bits / chain.run_chain with SIA for values.")
    return NODE_STEPS[cfg.kind]


def level_step(cfg: AggConfig):
    """Return the whole-level node-step function for ``cfg.kind``.

    Signature::

        fn(g [W,d], gamma_in [W,d], e [W,d], weight [W], participate [W],
           global_mask ([d] shared or [W,d] per-lane), q_budget ([W]|None),
           valid ([W]|None)) -> (gamma_out [W,d], e_new [W,d], HopStats [W])

    One call runs all W slots of a padded level schedule concurrently —
    this is what the plan executors (:func:`repro.agg.plan.execute`, the
    device lowering's level loop) step with. When the fused kernel path is
    on (:func:`fused_node_steps`) the level goes through the batched
    Pallas kernels of :mod:`repro.kernels.level`, skipping ``valid == 0``
    padding lanes; otherwise it is exactly the historic ``vmap`` of the
    scalar node step (bit-identical to the pre-fusion executors).
    """
    step = node_step(cfg)

    def run(g, gamma_in, e, weight, participate, global_mask,
            q_budget=None, valid=None):
        if fused_node_steps(cfg, weight, g, e, gamma_in):
            return _run_fused_level(cfg, g, gamma_in, e, weight,
                                    participate, global_mask, q_budget,
                                    valid)
        shared_mask = getattr(global_mask, "ndim", 1) == 1

        def one(g_r, gam_r, e_r, w_r, p_r, *rest):
            i = 0
            gm_r = global_mask
            if not shared_mask:
                gm_r = rest[i]
                i += 1
            qb_r = rest[i] if q_budget is not None else None
            ctx = NodeCtx(global_mask=gm_r, participate=p_r, q_budget=qb_r)
            return step(cfg, g_r, gam_r, e_r, w_r, ctx)

        args = [g, gamma_in, e, weight, participate]
        if not shared_mask:
            args.append(global_mask)
        if q_budget is not None:
            args.append(q_budget)
        gamma_out, e_new, stats = jax.vmap(one)(*args)
        if valid is not None:
            # same contract as the fused kernels: valid == 0 (padding)
            # lanes output zeros and count nothing, whatever garbage their
            # input rows hold — keeps the two modes interchangeable for
            # callers that don't route padding through zero dummy rows
            ok = valid > 0
            gamma_out = jnp.where(ok[:, None], gamma_out,
                                  jnp.zeros_like(gamma_out))
            e_new = jnp.where(ok[:, None], e_new, jnp.zeros_like(e_new))
            stats = jax.tree.map(
                lambda s: jnp.where(ok, s, jnp.zeros_like(s)), stats)
        return gamma_out, e_new, stats

    return run


def level_step_batched(cfg: AggConfig):
    """Whole-level node step over a cohort batch — one launch for B levels.

    Signature::

        fn(g [B,W,d], gamma_in [B,W,d], e [B,W,d], weight [B,W],
           participate [B,W],
           global_mask ([B,d] cohort-shared or [B,W,d] per-lane),
           q_budget ([B,W]|None), valid ([B,W]|None))
          -> (gamma_out [B,W,d], e_new [B,W,d], HopStats [B,W])

    B shape-identical cohorts flatten **cohort-major** to ``B*W`` lanes
    (cohort b owns lanes ``b*W .. (b+1)*W-1``) and run through a single
    :func:`level_step` launch — on the fused path that is ONE
    ``pallas_call`` per kernel stage for all cohorts, with per-cohort TC
    global masks streamed compact ([B, d], cohort-shared block spec)
    rather than vmapping the pallas_call. Every lane's math is row
    independent, so the result is bitwise identical, per cohort, to B
    sequential ``level_step`` calls (tests/test_batched_rounds.py pins
    this in interpret mode).
    """
    run1 = level_step(cfg)

    def run(g, gamma_in, e, weight, participate, global_mask,
            q_budget=None, valid=None):
        b, w, d = g.shape
        lanes = b * w

        def fl(x):
            return None if x is None else x.reshape((lanes,) + x.shape[2:])

        cohort_gm = getattr(global_mask, "ndim", 2) == 2   # [B, d]
        gf, gamf, ef = fl(g), fl(gamma_in), fl(e)
        wf, pf = fl(weight), fl(participate)
        qbf, vf = fl(q_budget), fl(valid)
        if cohort_gm and fused_node_steps(cfg, weight, g, e, gamma_in):
            gout, e_new, stats = _run_fused_level(
                cfg, gf, gamf, ef, wf, pf, _f32(global_mask), qbf, vf,
                cohorts=b)
        else:
            gm = (jnp.repeat(global_mask, w, axis=0) if cohort_gm
                  else fl(global_mask))
            gout, e_new, stats = run1(gf, gamf, ef, wf, pf, gm, qbf, vf)

        def unfl(x):
            return x.reshape((b, w) + x.shape[1:])

        return unfl(gout), unfl(e_new), jax.tree.map(unfl, stats)

    return run
