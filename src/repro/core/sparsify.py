"""Top-Q sparsification primitives.

Notation follows the paper: ``S(x, Q)`` returns the Top-Q (by magnitude)
sparsification of ``x`` (all other entries zeroed); ``s(x, Q)`` returns the
corresponding 0/1 mask.  Everything here is pure-functional, jit-safe, and
operates on flat 1-D vectors; pytree plumbing lives in :mod:`repro.core.api`.

Two implementations are provided:

* exact: the ``jax.lax.top_k`` support (by ``top_k`` on short vectors, by
  a counting select on long ones) — the oracle used by the simulator,
  tests and the train step;
* threshold: histogram + bisection (distributable; composes with sharding via
  a single ``psum`` of the histogram) — the production path, with the
  perf-critical histogram implemented as a Pallas kernel in
  :mod:`repro.kernels`.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import scopes

Array = jax.Array


# ---------------------------------------------------------------------------
# Exact Top-Q (oracle)
# ---------------------------------------------------------------------------
#
# Two lowerings of one selection: the Q largest magnitudes, ties at the
# Q-th broken toward the lower index (``lax.top_k``'s documented order).
# At large k the TPU lowers ``top_k`` to a full sort of the vector; the
# counting select finds the same support with streaming compare-and-count
# passes and no sort or scatter. Both give bitwise the same mask, so the
# choice is speed alone, made from the shape.

#: Vectors of at least this many entries take the counting select, shorter
#: ones ``lax.top_k``. On a TPU v5e the two cost the same at 2^16 entries
#: (Top-1 %), the select wins from 2^18 on (PERF.md §6).
_SELECT_MIN_D = 1 << 16
#: Bits resolved per counting pass (1, 2, 4 or 8): each pass counts
#: 2**bits - 1 candidates at once. On a TPU v5e at 129 M entries 2 bits
#: beat 1 and 4 (PERF.md §6).
_SELECT_DIGIT_BITS = 2

#: Trace-time record of the exact selections lowered: ``(path, W, d, q)``
#: → times traced, path ``"select"`` or ``"top_k"`` (W = 1 for a vector).
EXACT_PATHS: collections.Counter = collections.Counter()


def _topk_keep(x: Array, q: int) -> Array:
    _, idx = jax.lax.top_k(jnp.abs(x), q)
    return jnp.zeros(x.shape, bool).at[idx].set(True)


def _largest_passing(count, ok, nbits: int, batch: tuple) -> Array:
    """Largest ``v`` in ``[0, 2**nbits)`` with ``ok(count(v))``, uint32.

    ``count(c [...])`` gives a count per lane and ``ok`` holds
    for ``v = 0`` and fails from some ``v`` on (monotone), so each pass
    fixes one digit: it counts every nonzero value of the digit above the
    bits found so far, all in one streaming pass, and keeps the largest
    that passes. The digit width divides 32, so no candidate wraps.
    """
    b = _SELECT_DIGIT_BITS
    steps = -(-nbits // b)

    def body(s, v):
        shift = ((steps - 1 - s) * b).astype(jnp.uint32)
        # one reduction per candidate: XLA fuses them into one multi-output
        # pass over the vector (a [R, d] broadcast would not be)
        n = sum(ok(count(v + (jnp.uint32(j) << shift))).astype(jnp.uint32)
                for j in range(1, 1 << b))
        return v + (n << shift)

    return jax.lax.fori_loop(0, steps, body, jnp.zeros(batch, jnp.uint32))


def _select_keep(x: Array, q: int) -> Array:
    """The ``lax.top_k`` support of ``x`` ([..., d], 0 < q < d) by counting.

    ``key`` is the bit pattern of |x| as float32, monotone in |x|. One
    digit search finds the q-th largest key ``k`` (the largest with
    ``#{key >= k} >= q``); the ``r = q - #{key > k}`` ties at it are kept
    lowest index first, cut at the largest ``i`` with ``#{j < i : key_j =
    k} < r``, which a second search over the index bits finds.
    """
    batch, d = x.shape[:-1], x.shape[-1]
    key = jax.lax.bitcast_convert_type(jnp.abs(x.astype(jnp.float32)),
                                       jnp.uint32)
    kth = _largest_passing(
        lambda c: jnp.sum(key >= c[..., None], axis=-1, dtype=jnp.int32),
        lambda n: n >= q, 31, batch)[..., None]
    tie = key == kth
    r = q - jnp.sum(key > kth, axis=-1, dtype=jnp.int32)
    idx = jax.lax.iota(jnp.uint32, d)
    cut = _largest_passing(
        lambda c: jnp.sum(tie & (idx < c[..., None]), axis=-1,
                          dtype=jnp.int32),
        lambda n: n < r, max(1, (d - 1).bit_length()), batch)
    return (key > kth) | (tie & (idx <= cut[..., None]))


def _exact_keep(x: Array, q: int) -> Array:
    """Boolean Top-Q support of ``x`` ([d] or [W, d]) for 0 < q < d."""
    d = x.shape[-1]
    w = x.shape[0] if x.ndim == 2 else 1
    select = (d >= _SELECT_MIN_D and jnp.issubdtype(x.dtype, jnp.floating)
              and jnp.dtype(x.dtype).itemsize <= 4)
    EXACT_PATHS["select" if select else "top_k", w, d, q] += 1
    if x.ndim == 1:
        return (_select_keep if select else _topk_keep)(x, q)
    if not select:
        return jax.vmap(_topk_keep, (0, None))(x, q)
    if w == 1:      # a [1, d] array tiles one row per TPU vreg: count it flat
        return _select_keep(x[0], q)[None]
    return _select_keep(x, q)


@jax.named_scope(scopes.TAU_SEARCH)
def topq(x: Array, q: int) -> Array:
    """``S(x, Q)``: keep the Q largest-magnitude entries of ``x``, zero the rest.

    ``x`` is [d] or [W, d] (one selection per lane). Ties at the Q-th
    magnitude keep the lower indices first (``lax.top_k`` order). ``q``
    must be a static Python int (shapes are static under jit).
    """
    if q <= 0:
        return jnp.zeros_like(x)
    if q >= x.shape[-1]:
        return x
    return jnp.where(_exact_keep(x, q), x, 0)


@jax.named_scope(scopes.TAU_SEARCH)
def topq_mask(x: Array, q: int) -> Array:
    """``s(x, Q)``: the 0/1 float mask of the Top-Q support of ``x``."""
    if q <= 0:
        return jnp.zeros_like(x)
    if q >= x.shape[-1]:
        return jnp.ones_like(x)
    return _exact_keep(x, q).astype(x.dtype)


def support(x: Array) -> Array:
    """``1(x)``: indicator vector of the nonzero entries of ``x`` (float 0/1)."""
    return (x != 0).astype(x.dtype)


def mask_union(*masks: Array) -> Array:
    """``1(m_a + m_b + …)``: union of 0/1 masks, returned as float 0/1."""
    acc = masks[0]
    for m in masks[1:]:
        acc = acc + m
    return (acc > 0).astype(acc.dtype)


def nnz(x: Array) -> Array:
    """``‖x‖₀`` as an int32 scalar (traced, not static)."""
    return jnp.sum(x != 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Dynamic-budget Top-Q (traced q — per-node bandwidth-aware budgets)
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.TAU_SEARCH)
def _dynamic_keep(x: Array, q: Array) -> Array:
    """Boolean Top-q support of ``x`` for a *traced* budget ``q``.

    Single source of truth for both the value and mask sparsifiers: τ = the
    q-th largest magnitude by full sort, keep |x| ≥ τ. Ties at τ may keep
    slightly more than q entries (same over-selection contract as
    :func:`topq_by_threshold`); q ≤ 0 keeps nothing, q ≥ d everything.
    """
    d = x.shape[-1]
    qc = jnp.clip(jnp.asarray(q, jnp.int32), 0, d)
    mag = jnp.abs(x)
    tau = jnp.sort(mag)[::-1][jnp.maximum(qc - 1, 0)]
    return (mag >= tau) & (mag > 0) & (qc > 0)


def topq_dynamic(x: Array, q: Array) -> Array:
    """``S(x, q)`` with a traced scalar budget ``q`` (int32).

    ``lax.top_k`` needs a static k, so per-node budgets (one vmapped lane
    per aggregation-tree slot) go through :func:`_dynamic_keep` instead.
    """
    return jnp.where(_dynamic_keep(x, q), x, 0)


def topq_mask_dynamic(x: Array, q: Array) -> Array:
    """``s(x, q)`` 0/1 mask counterpart of :func:`topq_dynamic`."""
    return _dynamic_keep(x, q).astype(x.dtype)


# ---------------------------------------------------------------------------
# Threshold-based Top-Q (distributable)
# ---------------------------------------------------------------------------

def count_ge(mag: Array, taus: Array) -> Array:
    """``counts[j] = #{i : mag_i >= taus_j}`` — int32 [B].

    Pure-jnp reference; the Pallas kernel in
    ``repro.kernels.topq_threshold`` matches this contract and is swapped in
    via the ``count_fn`` argument of :func:`threshold_for_topq`.
    """
    return jnp.sum(mag[:, None] >= taus[None, :], axis=0).astype(jnp.int32)


def count_ge_batch(mag: Array, taus: Array) -> Array:
    """Batched :func:`count_ge`: ``counts[w, b] = #{i : mag_{w,i} >= taus_{w,b}}``.

    mag: [W, d]; taus: [W, B] → int32 [W, B]. Pure-jnp reference; the Pallas
    kernel ``repro.kernels.level.count_ge_level_pallas`` matches this
    contract (swapped in via ``count_fn``).
    """
    return jnp.sum(mag[:, :, None] >= taus[:, None, :],
                   axis=1).astype(jnp.int32)


def count_ge_presorted(smag: Array, taus: Array) -> Array:
    """Candidate counts against an already-sorted magnitude vector.

    ``counts[j] = #{i : smag_i >= taus_j} = d − #{i : smag_i < taus_j}``,
    resolved by B binary searches — exact float comparisons, so the
    returned integers are bit-identical to the O(d·B) broadcast of
    :func:`count_ge`. O(B·log d) per call: the whole multi-round bisection
    costs one O(d·log d) sort (hoisted out of the round scan by
    :func:`tau_operand`) plus rounds·B searches. This is the default host
    count for :func:`threshold_for_topq` — it replaces the per-round
    O(d·B) sweep (and the scatter-add rank histogram that XLA:CPU
    serializes) that dominated the threshold sparsifier's CPU round time.
    """
    d = smag.shape[-1]
    return (d - jnp.searchsorted(smag, taus, side="left")
            ).astype(jnp.int32)


def count_ge_sorted(mag: Array, taus: Array) -> Array:
    """:func:`count_ge` via sort + binary search (any ``taus`` order)."""
    return count_ge_presorted(jnp.sort(mag), taus)


def count_ge_sorted_batch(mag: Array, taus: Array) -> Array:
    """Batched :func:`count_ge_sorted`: [W, d] × [W, B] → int32 [W, B]."""
    return jax.vmap(count_ge_sorted)(mag, taus)


class TauOperand(NamedTuple):
    """The bisection operand of :func:`threshold_for_topq`, as callbacks.

    Decouples the τ search from a *materialized* magnitude vector: the
    fused node-step path (``repro.core.algorithms``) builds one of these
    from the raw node inputs ``(g, e, γ_in, w, participate[, m])`` so the
    count kernels reconstruct ``|…·(w·g + e) + …|`` tile-by-tile in VMEM —
    no HBM round-trip of the operand before (or during) the search.

    * ``count(taus)``  → int32 candidate counts ([B] or [W, B]); ``taus``
      are always nondecreasing per lane.
    * ``max_abs()``    → max |operand| (f32 scalar or [W]) — the initial
      bracket top. Implementations must use the same float expression as a
      materialized ``jnp.max(jnp.abs(x))`` so the two paths stay bitwise
      identical.
    * ``batched``      → whether the operand carries a [W] lane axis.
    * ``hist(tables)`` → one-pass joint digit histogram ``(D2, F)`` for
      ``tau_impl="hist"`` (see :func:`_hist_digits` for the contract);
      None disables the hist implementation for this operand.
    * ``materialize()`` → the dense operand itself (the exact/dynamic
      sparsifier paths need the full sort anyway).
    """

    count: Callable[[Array], Array]
    max_abs: Callable[[], Array]
    batched: bool
    hist: Optional[Callable] = None
    materialize: Optional[Callable[[], Array]] = None


def tau_operand(x: Array, count_fn=None) -> TauOperand:
    """Wrap a materialized ``x`` ([d] or [W, d]) as a :class:`TauOperand`."""
    batched = x.ndim == 2
    mag = jnp.abs(x.astype(jnp.float32))
    if count_fn is None:
        # sort ONCE at operand construction — a loop constant of the
        # bisection scan, so every round's counts are B binary searches
        smag = jnp.sort(mag, axis=-1)
        count_fn = (jax.vmap(count_ge_presorted) if batched
                    else count_ge_presorted)
        count = lambda taus: count_fn(smag, taus)           # noqa: E731
    else:
        count = lambda taus: count_fn(mag, taus)            # noqa: E731

    def max_abs():
        if not mag.size:
            return (jnp.zeros(mag.shape[:-1], jnp.float32) if batched
                    else jnp.float32(0))
        return jnp.max(mag, axis=-1) if batched else jnp.max(mag)

    def hist(tables):
        fn = jax.vmap(_hist_digits) if batched else _hist_digits
        return fn(mag, *tables)

    return TauOperand(count=count, max_abs=max_abs, batched=batched,
                      hist=hist, materialize=lambda: x)


# -- one-pass histogram bisection (tau_impl="hist") -------------------------
#
# The scan evaluates `rounds` sequential streaming passes. For rounds ≤ 2
# one pass suffices: bin every element by its round-1 digit d1 (which of
# the branch+1 round-1 brackets it falls in) and its round-2 digit d2
# (candidate count *within its own bracket*), accumulate the joint
# histogram D2[d1, d2], and reconstruct both rounds' candidate-count
# integers exactly:
#
#   counts1[j] = #{d1 >= j}                                (j = 1..branch)
#   counts2[j] = #{d1 = B, d2 >= j} + #{d1 >= B+2}
#              + (j < branch ? #{d1 = B+1} : F[B+1])       (B = jstar1)
#
# The cross-bracket terms are exact theorems about the f32 bracket
# arithmetic, not approximations: an element one bracket above B clears
# every round-2 candidate except possibly the top one (margin ≈ w2 =
# (hi-lo)/branch² versus rounding noise ≈ 2⁻²⁴·hi — safe for
# branch ≤ 1024), and that top comparison is resolved exactly by the
# per-element flag F (|x| >= tau_top of its own bracket). Elements below
# bracket B clear nothing (their magnitude is < new_lo(B), the smallest
# candidate). Zero padding lands in the never-read bin D2[0, 0].

_F32_MAX = float(jnp.finfo(jnp.float32).max)


def _hist_tables(lo: Array, hi: Array, branch: int):
    """Per-bracket round-2 tables, mirroring the scan's float ops exactly.

    Returns ``(tau1 [.., b], new_lo [.., b+1], w2 [.., b+1],
    top_shift [.., b+1])`` where entry ``b'`` of the per-bracket tables is
    what the scan would compute had round 1 selected ``jstar1 = b'``;
    ``top_shift[d] = tau_top[d-1]`` (the top round-2 candidate of the
    bracket *below* digit d; f32 max for d = 0, which no magnitude
    reaches) feeds the per-element flag F.
    """
    steps = jnp.arange(1, branch + 1, dtype=jnp.float32)
    bf = jnp.arange(0, branch + 1, dtype=jnp.float32)
    lo_e = jnp.expand_dims(lo, -1)
    w1 = (hi - lo) / branch
    w1_e = jnp.expand_dims(w1, -1)
    tau1 = lo_e + w1_e * steps                     # [.., b]
    new_lo = lo_e + bf * w1_e                      # [.., b+1]
    new_hi = new_lo + w1_e
    w2 = (new_hi - new_lo) / branch                # [.., b+1]
    tau_top = new_lo + w2 * jnp.float32(branch)    # [.., b+1]
    top_shift = jnp.concatenate(
        [jnp.full_like(tau_top[..., :1], _F32_MAX), tau_top[..., :branch]],
        axis=-1)
    return tau1, new_lo, w2, top_shift


def _hist_digits(mag: Array, tau1: Array, new_lo: Array, w2: Array,
                 top_shift: Array):
    """Digit histogram of a materialized 1-D ``mag`` (jnp reference).

    Returns ``(D2 [b+1, b+1] i32, F [b+1] i32)``: ``D2[r, c] = #{d1 = r,
    d2 = c}`` and ``F[r] = #{d1 = r, mag >= top_shift[r]}``. d1 is the
    round-1 candidate count per element (searchsorted — exact, taus
    nondecreasing); d2 the round-2 candidate count *within the element's
    own bracket* (binary search over the candidate index, valid because
    ``new_lo + w2·j`` is nondecreasing in j).
    """
    branch = tau1.shape[-1]
    d1 = jnp.searchsorted(tau1, mag, side="right").astype(jnp.int32)
    nl = new_lo[d1]
    w2e = w2[d1]
    te = top_shift[d1]
    # d2 = largest j in 0..b with mag >= nl + w2e·j (j = 0 vacuously true)
    lo_i = jnp.zeros_like(d1)
    hi_i = jnp.full_like(d1, branch + 1)
    for _ in range(max(1, math.ceil(math.log2(branch + 1)))):
        mid = (lo_i + hi_i) // 2
        pred = mag >= nl + w2e * mid.astype(jnp.float32)
        take = hi_i - lo_i > 1
        lo_i = jnp.where(take & pred, mid, lo_i)
        hi_i = jnp.where(take & ~pred, mid, hi_i)
    d2 = lo_i
    flag = (mag >= te).astype(jnp.int32)
    D2 = jnp.zeros((branch + 1, branch + 1), jnp.int32).at[d1, d2].add(1)
    F = jnp.zeros((branch + 1,), jnp.int32).at[d1].add(flag)
    return D2, F


def _hist_bisect(new_lo: Array, w2: Array, D2: Array, F: Array, q: int,
                 branch: int, rounds: int):
    """Reconstruct the scan's per-round counts and τ from ``(D2, F)``.

    Returns ``(tau, [counts_round1, ...])`` with the same integers and the
    same final float ops as the streaming scan (``new_lo``/``w2`` are the
    bracket tables of :func:`_hist_tables`).
    """
    A = jnp.sum(D2, axis=-1)                                 # #{d1 = r}
    zeros2 = jnp.zeros(A.shape[:-1] + (2,), A.dtype)
    suffA = jnp.cumsum(
        jnp.concatenate([A, zeros2], -1)[..., ::-1], axis=-1)[..., ::-1]
    c1 = suffA[..., 1:branch + 1]                            # [.., b]
    jstar1 = jnp.sum((c1 >= q).astype(jnp.int32), axis=-1)   # [..] 0..b
    counts = [c1]
    B = jstar1[..., None]
    nl_B = jnp.take_along_axis(new_lo, B, axis=-1)[..., 0]
    w2_B = jnp.take_along_axis(w2, B, axis=-1)[..., 0]
    if rounds == 1:
        return jnp.maximum(nl_B, 1e-30), counts
    S2 = jnp.cumsum(D2[..., ::-1], axis=-1)[..., ::-1]       # #{d1=r, d2>=c}
    rowS2 = jnp.take_along_axis(S2, B[..., None], axis=-2)[..., 0, :]
    zeros1 = jnp.zeros(A.shape[:-1] + (1,), A.dtype)
    a_next = jnp.take_along_axis(
        jnp.concatenate([A, zeros1], -1), B + 1, axis=-1)[..., 0]
    f_next = jnp.take_along_axis(
        jnp.concatenate([F, zeros1], -1), B + 1, axis=-1)[..., 0]
    s_next2 = jnp.take_along_axis(suffA, B + 2, axis=-1)[..., 0]
    is_top = jnp.arange(1, branch + 1) == branch
    c2 = (rowS2[..., 1:branch + 1] + s_next2[..., None]
          + jnp.where(is_top, f_next[..., None], a_next[..., None]))
    counts.append(c2)
    jstar2 = jnp.sum((c2 >= q).astype(jnp.int32), axis=-1)
    tau = nl_B + jstar2.astype(jnp.float32) * w2_B
    return jnp.maximum(tau, 1e-30), counts


@jax.named_scope(scopes.TAU_SEARCH)
def threshold_for_topq(
    x: Optional[Array],
    q: int,
    *,
    branch: int = 64,
    rounds: int = 3,
    axis_name: str | None = None,
    count_fn=None,
    operand_fn: Optional[TauOperand] = None,
    tau_impl: str = "scan",
    with_counts: bool = False,
) -> Array:
    """Magnitude threshold ``τ`` with ``count(|x| >= τ) ≈ q`` (always ≥ q).

    Branch-and-bisect: each round evaluates ``branch`` candidate thresholds
    inside the current bracket (one streaming pass over x) and narrows the
    bracket ``branch``-fold → resolution ``branch**rounds`` bins after
    ``rounds`` passes.

    When ``axis_name`` is given, candidate counts (and the bracket top) are
    ``psum``/``pmax``-reduced over that mesh axis so every shard computes the
    identical *global* threshold — this is how the paper's global Top-Q
    survives sharding (``q`` is then the global budget).

    Invariant maintained: ``count(|x| >= lo) >= q`` — the returned ``lo``
    therefore keeps at least q survivors (over-selection bounded by the ties
    inside one final-resolution bin; tests measure it).

    ``x`` may also be batched ``[W, d]`` (the fused whole-level node-step
    path): every lane runs its own bracket, ``count_fn`` then takes
    ``(mag [W, d], taus [W, B]) → [W, B]``, and a ``[W]`` vector of
    thresholds is returned — bitwise identical per lane to the 1-D path
    (same bracket arithmetic, integer candidate counts).

    ``operand_fn`` (a :class:`TauOperand`) replaces the materialized ``x``
    entirely — counts, bracket top and histogram all stream through its
    callbacks (the fused-operand kernel path); ``x`` may then be None.

    ``tau_impl``: "scan" (the streaming multi-pass oracle) or "hist"
    (rounds ≤ 2 only — one joint digit histogram replaces the sequential
    passes; per-round candidate counts and the returned τ are bit-identical
    to the scan, see :func:`_hist_bisect`).

    ``with_counts=True`` additionally returns the per-round candidate
    counts (post-``psum``), stacked [rounds, .., branch] — the hist-vs-scan
    parity tests key on these integers.

    On a single host (no ``axis_name``/``count_fn``/``operand_fn``/
    ``with_counts``) the scan runs count-free: one ``top_k(q)`` resolves
    the ``count >= q`` predicate for every candidate of every round, with
    bitwise-identical τ (see the inline comment).
    """
    if tau_impl not in ("scan", "hist"):
        raise ValueError(f"unknown tau_impl {tau_impl!r}")
    # Single-host shortcut: the bisection consumes counts ONLY through the
    # predicate count(τ_j) >= q, and #{|x| >= t} >= q  ⟺  t <= the q-th
    # largest |x| (exact float comparisons, ties included) — so one
    # ``lax.top_k(q)`` replaces every per-round count sweep (and the
    # operand construction entirely). The jstar integers, and therefore τ,
    # are bitwise identical to the counting scan. Invalid whenever counts
    # are observable (``with_counts``), mesh-reduced (per-shard q-th
    # values do not compose into the global predicate), or routed through
    # a caller-specified count path.
    kth = None
    if (tau_impl == "scan" and axis_name is None and operand_fn is None
            and count_fn is None and not with_counts):
        operand = None
        mag = jnp.abs(x.astype(jnp.float32))
        batched = x.ndim == 2
        d = mag.shape[-1]
        if not mag.size:
            hi = jnp.zeros(mag.shape[:-1], jnp.float32)
        else:
            hi = jnp.max(mag, axis=-1) if batched else jnp.max(mag)
        if q <= 0:
            kth = jnp.full(hi.shape, jnp.inf)        # count >= q always
        elif q > d:
            kth = jnp.full(hi.shape, -jnp.inf)       # count < q always
        else:
            # min over the top-q block == the q-th largest; NOT
            # ``[..., -1]`` — XLA:CPU rewrites topk+slice into a full
            # stable sort (30× slower than its TopK custom call)
            kth = jnp.min(jax.lax.top_k(mag, q)[0], axis=-1)
    else:
        operand = (tau_operand(x, count_fn) if operand_fn is None
                   else operand_fn)
        batched = operand.batched
        hi = operand.max_abs()
    if axis_name is not None:
        hi = jax.lax.pmax(hi, axis_name)
    # strictly above max ⇒ count(hi) = 0 < q; tiny floor handles all-zero x
    hi = jnp.maximum(hi, 1e-30) * jnp.float32(1 + 1e-6)
    lo = jnp.zeros_like(hi)

    if tau_impl == "hist":
        if rounds not in (1, 2):
            raise ValueError("tau_impl='hist' folds the whole search into "
                             "one histogram pass; rounds must be 1 or 2, "
                             f"got {rounds}")
        if branch > 1024:
            raise ValueError("tau_impl='hist' cross-bracket count exactness "
                             f"needs branch <= 1024, got {branch}")
        if operand.hist is None:
            raise ValueError("operand_fn has no hist implementation")
        tables = _hist_tables(lo, hi, branch)
        D2, F = operand.hist(tables)
        if axis_name is not None:
            D2 = jax.lax.psum(D2, axis_name)
            F = jax.lax.psum(F, axis_name)
        tau, counts = _hist_bisect(tables[1], tables[2], D2, F, q, branch,
                                   rounds)
        return (tau, jnp.stack(counts)) if with_counts else tau

    def round_body(carry, _):
        lo, hi = carry
        w = (hi - lo) / branch
        steps = jnp.arange(1, branch + 1, dtype=jnp.float32)
        taus = (lo[:, None] + w[:, None] * steps if batched
                else lo + w * steps)
        if kth is not None:
            keeps_q = (kth[..., None] if batched else kth) >= taus
            counts = None
        else:
            counts = operand.count(taus)
            if axis_name is not None:
                counts = jax.lax.psum(counts, axis_name)
            keeps_q = counts >= q
        # counts is non-increasing in tau; jstar = #{j : counts_j >= q} is
        # the largest candidate index (1-based) still keeping >= q.
        jstar = jnp.sum(keeps_q.astype(jnp.int32), axis=-1)
        new_lo = lo + jstar.astype(jnp.float32) * w
        new_hi = new_lo + w
        return (new_lo, new_hi), counts if with_counts else None

    (lo, hi), ys = jax.lax.scan(round_body, (lo, hi), None, length=rounds)
    tau = jnp.maximum(lo, 1e-30)
    return (tau, ys) if with_counts else tau


def topq_by_threshold(
    x: Array, q: int, *, branch: int = 64, rounds: int = 3,
    axis_name: str | None = None, count_fn=None, tau_impl: str = "scan",
) -> Array:
    """Approximate ``S(x, Q)`` via the bisection threshold (≥ q survivors)."""
    tau = threshold_for_topq(
        x, q, branch=branch, rounds=rounds, axis_name=axis_name,
        count_fn=count_fn, tau_impl=tau_impl)
    return jnp.where(jnp.abs(x) >= tau, x, 0)


# ---------------------------------------------------------------------------
# Compact sparse representation (static shapes)
# ---------------------------------------------------------------------------

def compact(x: Array, q: int) -> Tuple[Array, Array, Array]:
    """Dense → compact ``(values[q], indices[q], count)``.

    The q slots hold the nonzero entries of ``x`` (which must have ≤ q
    nonzeros for lossless round-trip — the CL algorithms guarantee this).
    Unused slots carry value 0 and index d (one-past-end sentinel), so a
    scatter-add of the padding is a no-op via drop semantics.
    """
    d = x.shape[-1]
    is_nz = x != 0
    # Order: nonzeros first (stable), then padding.
    order = jnp.argsort(~is_nz, stable=True)
    take = order[:q]
    vals = x[take]
    valid = is_nz[take]
    idx = jnp.where(valid, take, d).astype(jnp.int32)
    vals = jnp.where(valid, vals, 0)
    return vals, idx, jnp.sum(is_nz).astype(jnp.int32)


def scatter(vals: Array, idx: Array, d: int) -> Array:
    """Compact ``(values, indices)`` → dense length-d vector.

    Out-of-range (sentinel) indices are dropped.
    """
    out = jnp.zeros((d,), vals.dtype)
    return out.at[idx].add(vals, mode="drop")
