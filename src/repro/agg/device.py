"""Device-plan execution: lower any :class:`AggPlan` onto the shard_map ring.

The plan/execute API (:mod:`repro.agg.plan`) made every topology — chain,
permuted order, routed constellation tree, graph, or one step of a
:class:`~repro.agg.schedule.TopologySchedule` — compile to one canonical
padded ``(L, W)`` level schedule. This module is the missing half: the same
schedule drives a **multi-device** shard_map program, so non-chain
topologies are no longer simulator-only (the ROADMAP's "tree-aware
distributed ring"). Two lowerings share the level walk, the compact
``(values, indices)`` wire transport, and the §V bit accounting of the
rotated ring:

``run_plan_segments_local``
    The *rotated-segment* kernel — the tree generalization of
    :func:`repro.core.ring.rotated_ring_local`. Rank r holds client r's
    flat gradient, split into K segments; segment s executes the plan with
    every tree position relabeled by ``+s (mod K)`` ("rotated start
    ranks"), so each rank runs one node step per real slot per level and
    every ICI link is busy at every level. The parameter server for
    segment s is rank s — the round's aggregate comes out naturally
    ZeRO-sharded, exactly like the ring. On the chain plan
    (:func:`ring_chain_plan`) this *is* the rotated ring, collective for
    collective — ``rotated_ring_local`` now delegates here.

``run_plan_clients_local``
    The *client-per-rank* kernel — the paper-faithful federated mapping.
    Rank r is client r with its full flat vector; one level-synchronous
    round is executed jointly, and the result is **bit-exact** to host
    :func:`repro.agg.plan.execute` (same values, EF, per-client §V stats).
    This is the kernel behind ``Simulator(backend="device")`` and the
    device/host equivalence tests.

Routing: a level's payload must travel from the rank playing a node to the
rank playing its parent. Under segment rotation that offset —
``(parent − node) mod K`` — is *rank-independent*, so a level is exactly a
set of ``ppermute`` steps. When the plan is a trace-time constant the
kernel emits one ppermute per real slot (the chain plan reproduces the
ring's K hops). When the plan's arrays are **traced** jit arguments (a
``TopologySchedule`` swapping plans per round under one specialization)
the offsets are traced too, so the kernel routes every level through a
⌈log₂K⌉-round ppermute butterfly: round j shifts the whole payload bundle
by 2^j and each slot keeps the shifted copy iff bit j of its offset is
set. Same values either way; the butterfly trades ~log₂K× wire for a
single XLA executable serving every same-shape plan.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.agg.plan import AggPlan, RoundResult, compile_plan
from repro.core import sparsify as sp
from repro.core.algorithms import (AggConfig, AggKind, HopStats, NodeCtx,
                                   level_step, level_step_batched, node_step)
from repro.core.ring import RingStats
from repro.obs import scopes

Array = jax.Array

# Algorithms whose per-hop payload is bounded by the budget → eligible for
# compact (values, indices) wire transport, the paper's ω+⌈log₂d⌉ format
# (same rule as the ring; see repro.core.ring._send).
_COMPACT_KINDS = (AggKind.CL_SIA, AggKind.CL_TC_SIA)


def _wire_budget(cfg: AggConfig) -> int:
    if cfg.kind == AggKind.CL_TC_SIA:
        return cfg.q_global + cfg.q_local
    return cfg.q


def _compact_eligible(cfg: AggConfig, seg: int, budgeted: bool) -> bool:
    """Wire-format eligibility (identical to the historic ring rule).

    Threshold Top-Q keeps ≥ q survivors (ties inside the final bisection
    bin over-select), so the CL bound ‖γ‖₀ ≤ q that sizes the q compact
    wire slots does not hold — only the exact ``lax.top_k`` sparsifier may
    use the compact segment. Same reasoning excludes dynamic per-client
    budgets (sort-threshold over-selection on ties).
    """
    q = _wire_budget(cfg)
    return (cfg.kind in _COMPACT_KINDS and not budgeted
            and cfg.topq_impl == "exact" and q < seg // 2)


def _use_compact(cfg: AggConfig, seg: int, plan: AggPlan,
                 participate_present: bool, wire: str) -> bool:
    """Decide the wire format for one lowering.

    Compact ``(values[q], indices[q])`` needs the CL bound ‖γ‖₀ ≤ q to hold
    on *every* hop. A non-participating (or stranded-stub) node forwards its
    incoming γ unchanged — on a tree that γ is a **sum over children** and
    can exceed q, so compact would silently drop coordinates. Chains are
    safe for any straggler set (every node has ≤ 1 child, so a forwarded γ
    was itself compacted); general plans are safe only when every node
    transmits, i.e. no ``participate`` mask and an all-alive plan.
    ``wire="auto"`` proves one of those statically (traced plans fall back
    to dense); ``wire="compact"`` lets a caller with host-side knowledge
    (e.g. the simulator on an all-alive schedule) assert safety;
    ``wire="dense"`` forces the dense segment.
    """
    if wire == "dense":
        return False
    eligible = _compact_eligible(cfg, seg, plan.q_budget is not None)
    if wire == "compact":
        if (cfg.kind not in _COMPACT_KINDS or plan.q_budget is not None
                or cfg.topq_impl != "exact"):
            raise ValueError(
                f"wire='compact' needs a constant-length algorithm with the "
                f"exact Top-Q sparsifier and no dynamic budgets; got "
                f"{cfg.kind} (topq_impl={cfg.topq_impl!r}, "
                f"q_budget={'set' if plan.q_budget is not None else 'none'})")
        return eligible
    if wire != "auto":
        raise ValueError(f"unknown wire format {wire!r}")
    if not eligible or not _is_static_plan(plan):
        return False
    k = plan.num_clients
    par = np.asarray(plan.parent_row)
    internal = par[(np.asarray(plan.slot_mask) > 0) & (par < k)]
    chain_like = (internal.size == 0
                  or np.bincount(internal, minlength=k).max() <= 1)
    all_alive = bool(np.all(np.asarray(plan.alive) > 0))
    return chain_like or (not participate_present and all_alive)


def _is_static_plan(plan: AggPlan) -> bool:
    """True when the plan's arrays are trace-time constants."""
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree.leaves(plan))


def ring_chain_tree(num_ranks: int):
    """The rotated ring's chain as an ``AggTree`` (reversed path tree)."""
    from repro.topo.tree import PS, AggTree
    return AggTree(parent=tuple(range(1, num_ranks)) + (PS,))


@functools.lru_cache(maxsize=None)
def ring_chain_plan(num_ranks: int) -> AggPlan:
    """The rotated ring's chain as an :class:`AggPlan`.

    Visiting order of segment s is ranks ``s, s+1, …, s+K−1`` — i.e. the
    *reversed* path tree (client 0 deepest, client K−1 adjacent to the PS),
    whose every transport offset is +1: the plan-driven kernel emits the
    ring's single ``ppermute(+1)`` per level.
    """
    return compile_plan(ring_chain_tree(num_ranks))


# ---------------------------------------------------------------------------
# Wire transport
# ---------------------------------------------------------------------------

def _shift_perm(num_ranks: int, shift: int) -> list:
    return [(i, (i + shift) % num_ranks) for i in range(num_ranks)]


def _nest_vmap(fn, levels: int):
    for _ in range(levels):
        fn = jax.vmap(fn)
    return fn


@jax.named_scope(scopes.TRANSPORT)
def _send_static(cfg: AggConfig, payload: Array, seg: int, axis,
                 shift: int, compact: bool) -> Array:
    """One logical hop by a static ring shift (the ring's ``_send``).

    ``payload`` may carry leading batch axes (``[B, seg]`` cohort batches
    — B cohorts ride ONE ppermute per hop; compact transport compacts per
    trailing vector).
    """
    if shift == 0:
        return payload
    perm = _shift_perm(jax.lax.axis_size(axis), shift)
    if not compact:
        return jax.lax.ppermute(payload, axis, perm)
    lead = payload.ndim - 1
    q = _wire_budget(cfg)
    vals, idx, _ = _nest_vmap(lambda x: sp.compact(x, q), lead)(payload)
    vals = jax.lax.ppermute(vals.astype(jnp.dtype(cfg.wire_dtype)), axis,
                            perm)
    idx = jax.lax.ppermute(idx, axis, perm)
    return _nest_vmap(
        lambda v, i: sp.scatter(v.astype(jnp.float32), i, seg),
        lead)(vals, idx)


@jax.named_scope(scopes.TRANSPORT)
def _route_butterfly(cfg: AggConfig, payload: Array, offsets: Array,
                     seg: int, axis, compact: bool) -> Array:
    """Deliver ``payload[w]`` to rank ``r + offsets[w]`` for every rank r.

    Offsets are *traced* (plan-dependent) but rank-uniform per slot, so a
    ⌈log₂K⌉-round butterfly of whole-bundle ppermutes with per-slot bit
    selection realizes any shift pattern under one specialization.

    ``payload`` is ``[W, seg]``, or ``[W, B, seg]`` for a cohort batch —
    slots stay the leading axis (the bit selection broadcasts over the
    cohorts) and each butterfly round remains ONE ppermute of the whole
    bundle for all B cohorts.
    """
    K = jax.lax.axis_size(axis)
    rounds = max(1, math.ceil(math.log2(K))) if K > 1 else 0
    lead = payload.ndim - 1
    if compact:
        q = _wire_budget(cfg)
        vals, idx, _ = _nest_vmap(lambda x: sp.compact(x, q),
                                  lead)(payload)
        vals = vals.astype(jnp.dtype(cfg.wire_dtype))
        bundle = (vals, idx)
    else:
        bundle = (payload,)
    for j in range(rounds):
        perm = _shift_perm(K, 2 ** j)
        moved = tuple(jax.lax.ppermute(b, axis, perm) for b in bundle)
        take = ((offsets >> j) & 1) > 0                       # [W] bool
        bundle = tuple(
            jnp.where(take.reshape((-1,) + (1,) * (b.ndim - 1)), m, b)
            for b, m in zip(bundle, moved))
    if compact:
        vals, idx = bundle
        return _nest_vmap(lambda v, i: sp.scatter(
            v.astype(jnp.float32), i, seg), lead)(vals, idx)
    return bundle[0]


# ---------------------------------------------------------------------------
# Rotated-segment kernel (the ring generalization)
# ---------------------------------------------------------------------------

def _is_register_chain(plan: AggPlan, np_node, np_par) -> bool:
    """True for chain-structured plans: one slot per level, no padding, and
    level l's parent is level l+1's node (the delivery is consumed on the
    very next level), finishing at the PS. Such plans — the ring chain and
    every permuted chain order — need no inbox buffer."""
    L, W = plan.shape
    k = plan.num_clients
    if W != 1 or L != k or np.any(np.asarray(plan.slot_mask)[:, 0] <= 0):
        return False
    ids, par = np_node[:, 0], np_par[:, 0]
    return (all(par[l] == ids[l + 1] for l in range(L - 1))
            and par[L - 1] == k)


def _run_chain_register(cfg, plan, flat_local, ef_local, weight, *, axis,
                        np_node, np_par, global_mask_local, p_eff, qb,
                        compact):
    """Chain specialization: the historic rotated-ring register loop.

    Keeps the full-size buffers in their storage dtype (bf16 by default —
    a full f32 upcast here would materialize 2× the gradient shard);
    per-segment slices are upcast to f32 inside the loop.
    """
    K = jax.lax.axis_size(axis)
    r = jax.lax.axis_index(axis)
    n = flat_local.shape[0]
    seg = n // K
    L = plan.shape[0]
    x = flat_local.reshape(K, seg)
    ef = ef_local.reshape(K, seg)
    gm = (None if global_mask_local is None
          else global_mask_local.reshape(K, seg))

    step_fn = node_step(cfg)
    gamma = jnp.zeros((seg,), jnp.float32)
    bits = jnp.float32(0)
    nnz = jnp.float32(0)
    err = jnp.float32(0)
    for l in range(L):
        b, p = int(np_node[l, 0]), int(np_par[l, 0])
        s = jnp.mod(r - b, K)
        g_seg = x[s].astype(jnp.float32)
        e_seg = ef[s].astype(jnp.float32)
        m_seg = (jnp.zeros((seg,), jnp.float32) if gm is None
                 else gm[s].astype(jnp.float32))
        ctx = NodeCtx(global_mask=m_seg, participate=p_eff, q_budget=qb)
        gamma_out, e_new, st = step_fn(cfg, g_seg, gamma, e_seg, weight, ctx)
        ef = ef.at[s].set(e_new.astype(ef.dtype))
        bits = bits + st.bits
        nnz = nnz + st.nnz_out.astype(jnp.float32)
        err = err + st.err_sq
        shift = (-b) % K if p == K else (p - b) % K
        gamma = _send_static(cfg, gamma_out, seg, axis, shift, compact)
    # the final send was the ownership shift: rank r holds segment r
    return gamma, ef.reshape(n), RingStats(bits=bits, nnz=nnz, err_sq=err)


def run_plan_segments_local(
    cfg: AggConfig,
    plan: AggPlan,
    flat_local: Array,                # [n] this rank's gradient slice
    ef_local: Array,                  # [n] this rank's EF memory
    weight: Array,                    # scalar D_k
    *,
    axis,                             # mesh axis name or tuple (ring order)
    global_mask_local: Optional[Array] = None,   # [n] TCS mask slice
    participate: Optional[Array] = None,         # scalar 0/1
    transport: str = "auto",          # "auto" | "static" | "butterfly"
    wire: str = "auto",               # "auto" | "compact" | "dense"
) -> tuple[Array, Array, RingStats]:
    """Execute an AggPlan over the K-rank ring, one rotated copy per segment.

    Must be called inside shard_map with ``axis`` manual; ``n % K == 0``.
    Segment s runs the plan with tree positions relabeled by ``+s (mod K)``
    and its parameter server at rank s, so after the round rank r holds the
    fully-aggregated segment r — the ring's ownership layout. Per segment,
    the value path is bit-exact to :func:`repro.agg.plan.execute` on that
    segment with the client relabeling (tested), and on
    :func:`ring_chain_plan` the whole kernel is bit-exact to the historic
    ``rotated_ring_local``. Returns (final segment [n//K], new EF [n],
    summed RingStats).

    Memory: chain-structured static plans (the training default) take the
    register fast path — a single [seg] γ carry, no extra buffers, the
    historic ring's footprint. General trees need the [K+3, seg] f32 inbox
    (a parent may consume a child's delivery several levels later) plus
    padded-read copies of the gradient/EF shards — ~3 extra f32 shards per
    rank, the price of arbitrary topologies.

    Participation semantics: ``participate``, ``plan.alive``, and
    ``plan.q_budget`` are **physical-rank** properties here — rank r
    straggles, is stranded, or owns a narrow uplink as a device, in every
    segment, whatever plan position it plays (the host executor instead
    folds them per plan position; the per-segment host reference for a
    plan with stubs/budgets is therefore ``execute`` on an all-alive copy
    with ``(participate·alive)`` and ``q_budget`` relabeled by the
    segment's rotation — see tests/test_device_plan.py).
    """
    K = jax.lax.axis_size(axis)
    if plan.num_clients != K:
        raise ValueError(
            f"plan has {plan.num_clients} clients but the mesh axis "
            f"{axis!r} has {K} ranks")
    if plan.num_sinks != 1:
        raise ValueError(
            "the segments kernel runs single-sink plans; lower a "
            "NestedPlan through run_nested_segments_local")
    r = jax.lax.axis_index(axis)
    n = flat_local.shape[0]
    assert n % K == 0, (n, K)
    seg = n // K
    L, W = plan.shape

    if transport not in ("auto", "static", "butterfly"):
        raise ValueError(f"unknown transport {transport!r}")
    static = (_is_static_plan(plan) if transport == "auto"
              else transport == "static")
    if static and not _is_static_plan(plan):
        raise ValueError("transport='static' needs a trace-time-constant "
                         "plan (numpy arrays, not traced jit arguments)")
    np_node = np.asarray(plan.node_id) if static else None
    np_par = np.asarray(plan.parent_row) if static else None

    compact = _use_compact(cfg, seg, plan, participate is not None, wire)
    alive_r = jnp.asarray(plan.alive)[r]
    p_scalar = jnp.float32(1) if participate is None else participate.astype(
        jnp.float32)
    p_eff = p_scalar * alive_r
    qb = (None if plan.q_budget is None
          else jnp.asarray(plan.q_budget, jnp.int32)[r])

    if static and _is_register_chain(plan, np_node, np_par):
        # Chain-structured plan (every level's delivery is consumed at the
        # next level): carry γ in a single [seg] register exactly like the
        # historic hand-written ring — no inbox buffer, no concat copies.
        return _run_chain_register(cfg, plan, flat_local, ef_local, weight,
                                   axis=axis, np_node=np_node,
                                   np_par=np_par,
                                   global_mask_local=global_mask_local,
                                   p_eff=p_eff, qb=qb, compact=compact)

    node_id = jnp.asarray(plan.node_id)
    slot_mask = jnp.asarray(plan.slot_mask)
    parent_row = jnp.asarray(plan.parent_row)

    # Storage-dtype buffers, one zero row (K) backing padded-slot reads —
    # mirrors the host executor's dummy row.
    zrow = lambda buf: jnp.zeros((1, seg), buf.dtype)
    x_ext = jnp.concatenate([flat_local.reshape(K, seg)] +
                            [zrow(flat_local)])
    ef_ext = jnp.concatenate([ef_local.reshape(K, seg),
                              zrow(ef_local), zrow(ef_local)])   # K+1 trash
    gm_ext = None
    if global_mask_local is not None:
        gm_ext = jnp.concatenate([global_mask_local.reshape(K, seg)] +
                                 [zrow(global_mask_local)])

    # inbox rows: 0..K−1 per-segment incoming sums, K = this rank's PS
    # accumulator (segment r), K+1 = trash, K+2 = zero dummy (read-only).
    inbox = jnp.zeros((K + 3, seg), jnp.float32)

    lvl_fn = level_step(cfg)
    w_bcast = jnp.broadcast_to(jnp.asarray(weight, jnp.float32), (W,))
    p_bcast = jnp.broadcast_to(p_eff, (W,))
    qb_bcast = None if qb is None else jnp.broadcast_to(qb, (W,))
    bits = jnp.float32(0)
    nnz = jnp.float32(0)
    err = jnp.float32(0)

    for l in range(L):
        ids_l = node_id[l]                               # [W]
        mask_l = slot_mask[l]
        par_l = parent_row[l]
        valid = mask_l > 0
        s_w = jnp.mod(r - ids_l, K).astype(jnp.int32)    # my segment per slot
        s_read = jnp.where(valid, s_w, K)                # padding → zero row

        g_lvl = x_ext[s_read].astype(jnp.float32)
        e_lvl = ef_ext[s_read].astype(jnp.float32)
        gam_in = inbox[jnp.where(valid, s_w, K + 2)]
        m_lvl = (jnp.zeros((W, seg), jnp.float32) if gm_ext is None
                 else gm_ext[s_read].astype(jnp.float32))

        gamma_out, e_new, st = lvl_fn(g_lvl, gam_in, e_lvl, w_bcast,
                                      p_bcast, m_lvl, qb_bcast, mask_l)

        ef_ext = ef_ext.at[jnp.where(valid, s_w, K + 1)].set(
            e_new.astype(ef_ext.dtype))
        bits = bits + jnp.sum(st.bits * mask_l)
        nnz = nnz + jnp.sum(st.nnz_out.astype(jnp.float32) * mask_l)
        err = err + jnp.sum(st.err_sq * mask_l)

        payload = gamma_out * mask_l[:, None]
        is_ps = par_l == K
        if static:
            arrived = []
            for w in range(W):
                b = int(np_node[l, w])
                if b >= K:                               # padding slot
                    arrived.append(jnp.zeros((seg,), jnp.float32))
                    continue
                p = int(np_par[l, w])
                shift = (-b) % K if p == K else (p - b) % K
                arrived.append(_send_static(cfg, payload[w], seg, axis,
                                            shift, compact))
            arrived = jnp.stack(arrived)
        else:
            offsets = jnp.where(is_ps, jnp.mod(-ids_l, K),
                                jnp.mod(par_l - ids_l, K)).astype(jnp.int32)
            arrived = _route_butterfly(cfg, payload, offsets, seg, axis,
                                       compact)
        # receiver's inbox row: segment (r − parent) for ordinary slots,
        # the PS accumulator for PS slots, trash for padding — one
        # slot-ordered scatter-add, mirroring the host executor's.
        rows = jnp.where(valid,
                         jnp.where(is_ps, K, jnp.mod(r - par_l, K)),
                         K + 1).astype(jnp.int32)
        inbox = inbox.at[rows].add(arrived)

    final = inbox[K]
    return final, ef_ext[:K].reshape(n), RingStats(bits=bits, nnz=nnz,
                                                   err_sq=err)


# ---------------------------------------------------------------------------
# Client-per-rank kernel (bit-exact to host execute)
# ---------------------------------------------------------------------------

def run_plan_clients_local(
    cfg: AggConfig,
    plan: AggPlan,
    g_local: Array,                   # [d] this client's flat gradient
    ef_local: Array,                  # [d] this client's EF memory
    weight: Array,                    # scalar D_k
    *,
    axis,                             # mesh axis (one rank per client)
    global_mask: Optional[Array] = None,   # [d] TCS mask (replicated)
    participate: Optional[Array] = None,   # scalar 0/1
    wire: str = "auto",                    # "auto" | "compact" | "dense"
) -> tuple[Array, Array, HopStats]:
    """Execute an AggPlan with client k living on rank k (paper mapping).

    Must be called inside shard_map with ``axis`` manual and axis size ==
    ``plan.num_clients`` (a nested stage plan is first padded by
    :func:`_pad_plan_clients` so it names every rank but schedules only
    its real clients — the extra ranks never activate). Levels run in
    lockstep; each level the
    active ranks fold their gradient into their inbox and ship γ toward
    the rank playing their parent (compact wire for the CL algorithms).
    Bit-exact to host :func:`repro.agg.plan.execute` — same aggregate, EF
    rows, and per-client §V HopStats (returned for *this* rank's client).
    The sink aggregate is returned replicated on every rank: ``[d]`` for
    single-sink plans, ``[R, d]`` sink-ordered for forest plans (the
    stage form of a :class:`~repro.agg.nested.NestedPlan`).
    """
    K = jax.lax.axis_size(axis)
    if plan.num_clients != K:
        raise ValueError(
            f"plan has {plan.num_clients} clients but the mesh axis "
            f"{axis!r} has {K} ranks")
    r = jax.lax.axis_index(axis)
    d = g_local.shape[0]
    L, W = plan.shape

    node_id = jnp.asarray(plan.node_id)
    slot_mask = jnp.asarray(plan.slot_mask)
    parent_row = jnp.asarray(plan.parent_row)
    # dtype-faithful to the host executor: participation, masks, and the
    # inbox all live in the gradient dtype, exactly as execute()'s
    # g_ext/e_ext/inbox do — bit-exactness holds for bf16 inputs too
    dt = g_local.dtype
    alive_r = jnp.asarray(plan.alive, dt)[r]
    p_scalar = jnp.ones((), dt) if participate is None else participate
    p_eff = p_scalar * alive_r
    qb = (None if plan.q_budget is None
          else jnp.asarray(plan.q_budget, jnp.int32)[r])
    compact = _use_compact(cfg, d, plan, participate is not None, wire)
    if wire == "auto" and jnp.dtype(cfg.wire_dtype) != jnp.float32:
        # a quantizing wire (ω=16 bf16 knob) breaks host parity — this
        # kernel's contract; wire="compact" still opts in explicitly
        compact = False
    q_wire = _wire_budget(cfg)

    gm = jnp.zeros((d,), dt) if global_mask is None else global_mask
    step_fn = node_step(cfg)
    ctx = NodeCtx(global_mask=gm, participate=p_eff, q_budget=qb)

    # buf rows: 0 = my inbox, 1..R = the (replicated) sink accumulators
    # (R = 1: the PS), R+1 = trash
    r_sinks = plan.num_sinks
    buf = jnp.zeros((2 + r_sinks, d), dt)
    e_cur = ef_local
    zero_i = jnp.int32(0)
    my_stats = HopStats(nnz_out=zero_i, nnz_global=zero_i, nnz_local=zero_i,
                        bits=jnp.float32(0), err_sq=jnp.float32(0))

    for l in range(L):
        ids_l = node_id[l]
        valid = slot_mask[l] > 0                         # [W]
        is_me = (ids_l == r) & valid
        active = jnp.any(is_me)

        gamma_out, e_new, st = step_fn(cfg, g_local, buf[0], e_cur, weight,
                                       ctx)
        # no down-cast: the host executor returns EF in the node step's
        # (possibly promoted) dtype, and where() promotes e_cur to match
        e_cur = jnp.where(active, e_new, e_cur)
        my_stats = jax.tree.map(
            lambda acc, s: jnp.where(active, s, acc), my_stats, st)

        with jax.named_scope(scopes.TRANSPORT):
            payload = gamma_out * active.astype(gamma_out.dtype)
            if compact:
                vals, idx, _ = sp.compact(payload, q_wire)
                all_vals = jax.lax.all_gather(
                    vals.astype(jnp.dtype(cfg.wire_dtype)), axis)
                all_idx = jax.lax.all_gather(idx, axis)
                def from_rank(b):
                    return sp.scatter(all_vals[b].astype(payload.dtype),
                                      all_idx[b], d)
            else:
                all_pay = jax.lax.all_gather(payload, axis)  # [K, d]
                def from_rank(b):
                    return all_pay[b]

            # deliver in slot order (the host executor's scatter order): row 0
            # if the sender's parent is me, rows 1..R if it is a sink, else
            # trash.
            b_clip = jnp.clip(ids_l, 0, K - 1)
            arrived = jax.vmap(from_rank)(b_clip) * slot_mask[l][:, None]
        par_l = parent_row[l]
        p_clients = plan.num_clients
        rows = jnp.where(
            valid & (par_l == r), 0,
            jnp.where(valid & (par_l >= p_clients)
                      & (par_l < p_clients + r_sinks),
                      1 + par_l - p_clients,
                      1 + r_sinks)).astype(jnp.int32)
        # mixed-dtype add on purpose: the host executor scatter-adds the
        # (possibly f32-promoted) γ into the grads-dtype inbox, and jax's
        # duplicate-index combining differs from pre-casting the updates —
        # pre-casting here would be one bf16 ulp off the host result
        buf = buf.at[rows].add(arrived)

    return (buf[1] if r_sinks == 1 else buf[1:1 + r_sinks]), e_cur, my_stats


# ---------------------------------------------------------------------------
# Host-side wrapper: full rounds over a client mesh
# ---------------------------------------------------------------------------

def client_mesh(num_clients: int, axis: str = "clients"):
    """1-D mesh with one device per client (first K local devices)."""
    devs = jax.devices()
    if len(devs) < num_clients:
        raise ValueError(
            f"device plan needs {num_clients} devices, have {len(devs)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_clients} before importing jax to fake them on CPU)")
    return jax.sharding.Mesh(np.asarray(devs[:num_clients]), (axis,))


def execute_sharded(
    cfg: AggConfig,
    plan: AggPlan,
    grads: Array,                  # [K, d] per-client effective gradients
    e: Array,                      # [K, d] EF memory
    weights: Array,                # [K]    D_k
    *,
    mesh=None,
    global_mask: Optional[Array] = None,
    participate: Optional[Array] = None,
    wire: str = "auto",
) -> RoundResult:
    """One aggregation round on devices — drop-in for host ``execute``.

    Shards clients one-per-device over ``mesh`` (default:
    :func:`client_mesh`), runs :func:`run_plan_clients_local`, and returns
    the same :class:`~repro.agg.plan.RoundResult` contract, bit-exact to
    the host executor. Jit-friendly: the plan rides through as a traced
    pytree argument, so every same-shape plan of a
    :class:`~repro.agg.schedule.TopologySchedule` reuses one trace.
    """
    k, d = grads.shape
    if plan.num_clients != k:
        raise ValueError(f"plan has {plan.num_clients} clients, grads {k}")
    if mesh is None:
        mesh = client_mesh(k)
    axis = mesh.axis_names[0]
    from jax.sharding import PartitionSpec as P

    has_part = participate is not None
    part = (jnp.ones((k,), grads.dtype) if participate is None
            else participate)
    gmask = (jnp.zeros((d,), grads.dtype) if global_mask is None
             else global_mask)

    # resolve the wire format here, where the plan may still be a host
    # constant — inside the shard_map body it is always traced; auto never
    # picks a quantizing wire (host parity), wire="compact" may
    wire_fmt = ("compact" if _use_compact(cfg, d, plan, has_part, wire)
                and (wire == "compact"
                     or jnp.dtype(cfg.wire_dtype) == jnp.float32)
                else "dense")

    def body(plan, g_l, e_l, w_l, part_l, gm):
        agg, e_new, st = run_plan_clients_local(
            cfg, plan, g_l[0], e_l[0], w_l[0], axis=axis, global_mask=gm,
            participate=part_l[0] if has_part else None, wire=wire_fmt)
        return agg, e_new[None], jax.tree.map(lambda s: s[None], st)

    plan_specs = jax.tree.map(lambda _: P(), plan)
    stats_specs = jax.tree.map(lambda _: P(axis), HopStats(
        0, 0, 0, 0., 0.))
    agg, e_new, stats = compat.shard_map(
        body, mesh=mesh,
        in_specs=(plan_specs, P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P(axis), stats_specs),
        axis_names={axis},
    )(plan, grads, e, weights, part, gmask)
    return RoundResult(aggregate=agg, e_new=e_new, stats=stats)


# ---------------------------------------------------------------------------
# Cohort-batched lowerings: B multi-tenant rounds ride one collective
# ---------------------------------------------------------------------------

def _run_chain_register_batched(cfg, plan, flat_local, ef_local, weight, *,
                                axis, np_node, np_par, global_mask_local,
                                p_eff, qb, compact):
    """Cohort-batched chain register loop: γ is a ``[B, seg]`` carry.

    Same hop schedule as :func:`_run_chain_register`, but the B cohorts run
    the level as one :func:`level_step` launch (lanes = B) and every hop is
    ONE ``ppermute`` of the ``[B, seg]`` register — the collective count of
    the sequential ring, whatever B is.
    """
    K = jax.lax.axis_size(axis)
    r = jax.lax.axis_index(axis)
    b_coh, n = flat_local.shape
    seg = n // K
    L = plan.shape[0]
    x = flat_local.reshape(b_coh, K, seg)
    ef = ef_local.reshape(b_coh, K, seg)
    gm = (None if global_mask_local is None
          else global_mask_local.reshape(b_coh, K, seg))

    lvl_fn = level_step(cfg)
    gamma = jnp.zeros((b_coh, seg), jnp.float32)
    bits = jnp.zeros((b_coh,), jnp.float32)
    nnz = jnp.zeros((b_coh,), jnp.float32)
    err = jnp.zeros((b_coh,), jnp.float32)
    for l in range(L):
        b, p = int(np_node[l, 0]), int(np_par[l, 0])
        s = jnp.mod(r - b, K)
        g_seg = x[:, s].astype(jnp.float32)
        e_seg = ef[:, s].astype(jnp.float32)
        m_seg = (jnp.zeros((b_coh, seg), jnp.float32) if gm is None
                 else gm[:, s].astype(jnp.float32))
        gamma_out, e_new, st = lvl_fn(g_seg, gamma, e_seg, weight, p_eff,
                                      m_seg, qb)
        ef = ef.at[:, s].set(e_new.astype(ef.dtype))
        bits = bits + st.bits
        nnz = nnz + st.nnz_out.astype(jnp.float32)
        err = err + st.err_sq
        shift = (-b) % K if p == K else (p - b) % K
        gamma = _send_static(cfg, gamma_out, seg, axis, shift, compact)
    return gamma, ef.reshape(b_coh, n), RingStats(bits=bits, nnz=nnz,
                                                  err_sq=err)


def run_plan_segments_batched(
    cfg: AggConfig,
    plan: AggPlan,
    flat_local: Array,                # [B, n] this rank's cohort slices
    ef_local: Array,                  # [B, n] EF memories
    weight: Array,                    # [B] per-cohort D_k
    *,
    axis,
    global_mask_local: Optional[Array] = None,   # [B, n]
    participate: Optional[Array] = None,         # [B] 0/1
    transport: str = "auto",
    wire: str = "auto",
) -> tuple[Array, Array, RingStats]:
    """Cohort-batched :func:`run_plan_segments_local` — one shared plan,
    B tenants per rank, one ppermute wavefront per level.

    Every cohort runs the plan exactly as the sequential kernel would; the
    payloads stack to ``[B, seg]`` (chain register) / ``[W, B, seg]``
    (butterfly bundle) so each hop or butterfly round stays a single
    collective for all B cohorts. Per cohort the result is bitwise what
    the sequential kernel returns. Returns ``([B, seg], [B, n],``
    :class:`RingStats` with ``[B]`` leaves``)``.
    """
    if jnp.ndim(jnp.asarray(plan.node_id)) == 3:
        raise ValueError("the batched segments kernel runs one shared "
                         "plan; stacked per-cohort plans are a host "
                         "(execute_batched) feature")
    K = jax.lax.axis_size(axis)
    if plan.num_clients != K:
        raise ValueError(
            f"plan has {plan.num_clients} clients but the mesh axis "
            f"{axis!r} has {K} ranks")
    if plan.num_sinks != 1:
        raise ValueError("the batched segments kernel runs single-sink "
                         "plans")
    r = jax.lax.axis_index(axis)
    b_coh, n = flat_local.shape
    assert n % K == 0, (n, K)
    seg = n // K
    L, W = plan.shape

    if transport not in ("auto", "static", "butterfly"):
        raise ValueError(f"unknown transport {transport!r}")
    static = (_is_static_plan(plan) if transport == "auto"
              else transport == "static")
    if static and not _is_static_plan(plan):
        raise ValueError("transport='static' needs a trace-time-constant "
                         "plan (numpy arrays, not traced jit arguments)")
    np_node = np.asarray(plan.node_id) if static else None
    np_par = np.asarray(plan.parent_row) if static else None

    compact = _use_compact(cfg, seg, plan, participate is not None, wire)
    alive_r = jnp.asarray(plan.alive)[r]
    p_vec = (jnp.ones((b_coh,), jnp.float32) if participate is None
             else participate.astype(jnp.float32))
    p_eff = p_vec * alive_r
    qb = (None if plan.q_budget is None
          else jnp.broadcast_to(jnp.asarray(plan.q_budget, jnp.int32)[r],
                                (b_coh,)))

    if static and _is_register_chain(plan, np_node, np_par):
        return _run_chain_register_batched(
            cfg, plan, flat_local, ef_local, weight, axis=axis,
            np_node=np_node, np_par=np_par,
            global_mask_local=global_mask_local, p_eff=p_eff, qb=qb,
            compact=compact)

    node_id = jnp.asarray(plan.node_id)
    slot_mask = jnp.asarray(plan.slot_mask)
    parent_row = jnp.asarray(plan.parent_row)

    zrow = lambda buf: jnp.zeros((b_coh, 1, seg), buf.dtype)
    x_ext = jnp.concatenate([flat_local.reshape(b_coh, K, seg),
                             zrow(flat_local)], axis=1)
    ef_ext = jnp.concatenate([ef_local.reshape(b_coh, K, seg),
                              zrow(ef_local), zrow(ef_local)], axis=1)
    gm_ext = None
    if global_mask_local is not None:
        gm_ext = jnp.concatenate([global_mask_local.reshape(b_coh, K, seg),
                                  zrow(global_mask_local)], axis=1)

    inbox = jnp.zeros((b_coh, K + 3, seg), jnp.float32)

    lvl_fn = level_step_batched(cfg)
    w_bcast = jnp.broadcast_to(jnp.asarray(weight, jnp.float32)[:, None],
                               (b_coh, W))
    p_bcast = jnp.broadcast_to(p_eff[:, None], (b_coh, W))
    qb_bcast = (None if qb is None
                else jnp.broadcast_to(qb[:, None], (b_coh, W)))
    bits = jnp.zeros((b_coh,), jnp.float32)
    nnz = jnp.zeros((b_coh,), jnp.float32)
    err = jnp.zeros((b_coh,), jnp.float32)

    for l in range(L):
        ids_l = node_id[l]                               # [W]
        mask_l = slot_mask[l]
        par_l = parent_row[l]
        valid = mask_l > 0
        s_w = jnp.mod(r - ids_l, K).astype(jnp.int32)
        s_read = jnp.where(valid, s_w, K)

        g_lvl = x_ext[:, s_read].astype(jnp.float32)     # [B, W, seg]
        e_lvl = ef_ext[:, s_read].astype(jnp.float32)
        gam_in = inbox[:, jnp.where(valid, s_w, K + 2)]
        m_lvl = (jnp.zeros((b_coh, W, seg), jnp.float32) if gm_ext is None
                 else gm_ext[:, s_read].astype(jnp.float32))
        valid_b = jnp.broadcast_to(mask_l, (b_coh, W))

        gamma_out, e_new, st = lvl_fn(g_lvl, gam_in, e_lvl, w_bcast,
                                      p_bcast, m_lvl, qb_bcast, valid_b)

        rows_ef = jnp.where(valid, s_w, K + 1)
        ef_ext = jax.vmap(lambda efc, en: efc.at[rows_ef].set(
            en.astype(ef_ext.dtype)))(ef_ext, e_new)
        bits = bits + jnp.sum(st.bits * mask_l, axis=1)
        nnz = nnz + jnp.sum(st.nnz_out.astype(jnp.float32) * mask_l,
                            axis=1)
        err = err + jnp.sum(st.err_sq * mask_l, axis=1)

        payload = gamma_out * mask_l[None, :, None]      # [B, W, seg]
        is_ps = par_l == K
        if static:
            arrived = []
            for w in range(W):
                b = int(np_node[l, w])
                if b >= K:                               # padding slot
                    arrived.append(jnp.zeros((b_coh, seg), jnp.float32))
                    continue
                p = int(np_par[l, w])
                shift = (-b) % K if p == K else (p - b) % K
                arrived.append(_send_static(cfg, payload[:, w], seg, axis,
                                            shift, compact))
            arrived = jnp.stack(arrived, axis=1)         # [B, W, seg]
        else:
            offsets = jnp.where(is_ps, jnp.mod(-ids_l, K),
                                jnp.mod(par_l - ids_l, K)).astype(jnp.int32)
            arrived = jnp.moveaxis(
                _route_butterfly(cfg, jnp.moveaxis(payload, 0, 1), offsets,
                                 seg, axis, compact), 0, 1)
        rows = jnp.where(valid,
                         jnp.where(is_ps, K, jnp.mod(r - par_l, K)),
                         K + 1).astype(jnp.int32)
        inbox = jax.vmap(lambda ib, ar: ib.at[rows].add(ar))(inbox, arrived)

    final = inbox[:, K]
    return final, ef_ext[:, :K].reshape(b_coh, n), RingStats(
        bits=bits, nnz=nnz, err_sq=err)


def run_plan_clients_batched(
    cfg: AggConfig,
    plan: AggPlan,
    g_local: Array,                   # [B, d] this client's cohort grads
    ef_local: Array,                  # [B, d] EF memories
    weight: Array,                    # [B] per-cohort D_k
    *,
    axis,
    global_mask: Optional[Array] = None,   # [B, d] per-cohort TCS masks
    participate: Optional[Array] = None,   # [B] 0/1
    wire: str = "auto",
) -> tuple[Array, Array, HopStats]:
    """Cohort-batched :func:`run_plan_clients_local` — B tenants per rank.

    ``plan`` is shared ``[L, W]`` or stacked ``[B, L, W]``
    (:func:`repro.agg.plan.stack_plans`); either way each level is ONE
    :func:`level_step` launch (lanes = B) plus ONE ``all_gather`` of the
    ``[B, d]`` payload stack for all cohorts. Per cohort, bit-exact to the
    sequential kernel and hence to host ``execute``. Returns the sink
    aggregates ``[B, d]`` (or ``[B, R, d]``), EF ``[B, d]``, and this
    rank's per-cohort :class:`HopStats` (``[B]`` leaves).
    """
    K = jax.lax.axis_size(axis)
    if plan.num_clients != K:
        raise ValueError(
            f"plan has {plan.num_clients} clients but the mesh axis "
            f"{axis!r} has {K} ranks")
    r = jax.lax.axis_index(axis)
    b_coh, d = g_local.shape

    node_id = jnp.asarray(plan.node_id)
    slot_mask = jnp.asarray(plan.slot_mask)
    parent_row = jnp.asarray(plan.parent_row)
    stacked = node_id.ndim == 3
    if stacked and node_id.shape[0] != b_coh:
        raise ValueError(f"stacked plan has {node_id.shape[0]} cohorts, "
                         f"inputs {b_coh}")
    L, W = plan.shape[-2:]
    lvl = lambda a, l: a[:, l] if a.ndim == 3 else a[l]   # [B, W] | [W]

    dt = g_local.dtype
    alive = jnp.asarray(plan.alive, dt)
    alive_r = alive[:, r] if alive.ndim == 2 else jnp.broadcast_to(
        alive[r], (b_coh,))
    p_vec = jnp.ones((b_coh,), dt) if participate is None else participate
    p_eff = p_vec * alive_r
    if plan.q_budget is None:
        qb = None
    else:
        qbs = jnp.asarray(plan.q_budget, jnp.int32)
        qb = qbs[:, r] if qbs.ndim == 2 else jnp.broadcast_to(
            qbs[r], (b_coh,))
    compact = _use_compact(cfg, d, plan, participate is not None, wire)
    if wire == "auto" and jnp.dtype(cfg.wire_dtype) != jnp.float32:
        compact = False
    q_wire = _wire_budget(cfg)

    gm = jnp.zeros((b_coh, d), dt) if global_mask is None else global_mask
    lvl_fn = level_step(cfg)

    r_sinks = plan.num_sinks
    buf = jnp.zeros((b_coh, 2 + r_sinks, d), dt)
    e_cur = ef_local
    zero_i = jnp.zeros((b_coh,), jnp.int32)
    my_stats = HopStats(nnz_out=zero_i, nnz_global=zero_i,
                        nnz_local=zero_i,
                        bits=jnp.zeros((b_coh,), jnp.float32),
                        err_sq=jnp.zeros((b_coh,), jnp.float32))

    for l in range(L):
        ids_l = jnp.broadcast_to(lvl(node_id, l), (b_coh, W))
        mask_l = jnp.broadcast_to(lvl(slot_mask, l), (b_coh, W))
        par_l = jnp.broadcast_to(lvl(parent_row, l), (b_coh, W))
        valid = mask_l > 0
        active = jnp.any((ids_l == r) & valid, axis=1)   # [B]

        gamma_out, e_new, st = lvl_fn(g_local, buf[:, 0], e_cur, weight,
                                      p_eff, gm, qb)
        e_cur = jnp.where(active[:, None], e_new, e_cur)
        my_stats = jax.tree.map(
            lambda acc, s: jnp.where(active, s, acc), my_stats, st)

        with jax.named_scope(scopes.TRANSPORT):
            payload = gamma_out * active[:, None].astype(gamma_out.dtype)
            b_clip = jnp.clip(ids_l, 0, K - 1)               # [B, W]
            if compact:
                vals, idx, _ = jax.vmap(
                    lambda x: sp.compact(x, q_wire))(payload)
                all_vals = jax.lax.all_gather(
                    vals.astype(jnp.dtype(cfg.wire_dtype)), axis)  # [K, B, q]
                all_idx = jax.lax.all_gather(idx, axis)
                sel = lambda a: jnp.take_along_axis(
                    jnp.moveaxis(a, 0, 1), b_clip[:, :, None], axis=1)
                arrived = jax.vmap(jax.vmap(
                    lambda v, i: sp.scatter(v.astype(payload.dtype), i, d)))(
                        sel(all_vals), sel(all_idx))         # [B, W, d]
            else:
                all_pay = jax.lax.all_gather(payload, axis)  # [K, B, d]
                arrived = jnp.take_along_axis(
                    jnp.moveaxis(all_pay, 0, 1), b_clip[:, :, None], axis=1)
        arrived = arrived * mask_l[:, :, None]
        p_clients = plan.num_clients
        rows = jnp.where(
            valid & (par_l == r), 0,
            jnp.where(valid & (par_l >= p_clients)
                      & (par_l < p_clients + r_sinks),
                      1 + par_l - p_clients,
                      1 + r_sinks)).astype(jnp.int32)    # [B, W]
        # per-cohort slot-ordered scatter-add, same (intentionally
        # mixed-dtype) duplicate combining as the sequential kernel
        buf = jax.vmap(lambda bc, rc, ac: bc.at[rc].add(ac))(
            buf, rows, arrived)

    agg = buf[:, 1] if r_sinks == 1 else buf[:, 1:1 + r_sinks]
    return agg, e_cur, my_stats


def execute_sharded_batched(
    cfg: AggConfig,
    plan: AggPlan,
    grads: Array,                  # [B, K, d] per-cohort client gradients
    e: Array,                      # [B, K, d] EF memories
    weights: Array,                # [B, K]
    *,
    mesh=None,
    global_mask: Optional[Array] = None,   # [B, d]
    participate: Optional[Array] = None,   # [B, K]
    wire: str = "auto",
) -> RoundResult:
    """B cohort rounds on devices as ONE shard_map launch — the device twin
    of :func:`repro.agg.plan.execute_batched`.

    Clients shard one-per-device exactly as :func:`execute_sharded`; the
    cohort axis stays local to every rank, so each level still costs one
    ``all_gather`` however many tenants ride it — this is where the
    multi-tenant throughput win lives. Per cohort, bit-exact to
    ``execute_sharded`` (and hence host ``execute``) on that cohort's
    inputs. Returns a :class:`RoundResult` with a leading cohort axis.
    """
    b, k, d = grads.shape
    if plan.num_clients != k:
        raise ValueError(f"plan has {plan.num_clients} clients, grads {k}")
    if mesh is None:
        mesh = client_mesh(k)
    axis = mesh.axis_names[0]
    from jax.sharding import PartitionSpec as P

    has_part = participate is not None
    part = (jnp.ones((b, k), grads.dtype) if participate is None
            else participate)
    gmask = (jnp.zeros((b, d), grads.dtype) if global_mask is None
             else global_mask)

    wire_fmt = ("compact" if _use_compact(cfg, d, plan, has_part, wire)
                and (wire == "compact"
                     or jnp.dtype(cfg.wire_dtype) == jnp.float32)
                else "dense")

    def body(plan, g_l, e_l, w_l, part_l, gm):
        agg, e_new, st = run_plan_clients_batched(
            cfg, plan, g_l[:, 0], e_l[:, 0], w_l[:, 0], axis=axis,
            global_mask=gm,
            participate=part_l[:, 0] if has_part else None, wire=wire_fmt)
        return agg, e_new[:, None], jax.tree.map(lambda s: s[:, None], st)

    plan_specs = jax.tree.map(lambda _: P(), plan)
    stats_specs = jax.tree.map(lambda _: P(None, axis), HopStats(
        0, 0, 0, 0., 0.))
    agg, e_new, stats = compat.shard_map(
        body, mesh=mesh,
        in_specs=(plan_specs, P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P()),
        out_specs=(P(), P(None, axis), stats_specs),
        axis_names={axis},
    )(plan, grads, e, weights, part, gmask)
    return RoundResult(aggregate=agg, e_new=e_new, stats=stats)


# ---------------------------------------------------------------------------
# Nested (staged) plans on the shard_map ring — one mesh axis per stage
# ---------------------------------------------------------------------------

def run_nested_segments_local(
    cfg: AggConfig,
    nested,                           # NestedPlan (repro.agg.nested)
    flat_local: Array,                # [n] this rank's gradient slice
    ef_local: Array,                  # [n] client-tier EF memory
    stage_ef_local,                   # per-stage EF slices, stages ≥ 1:
                                      # stage s is [n // prod(K_0..K_{s-1})]
    weight: Array,                    # scalar D_k (stage-0 fold)
    *,
    axes,                             # one mesh axis name per stage,
                                      # stage-0 axis first (("data","pod"))
    global_mask_local: Optional[Array] = None,   # [n] TCS mask slice
    participate: Optional[Array] = None,         # scalar 0/1 (stage 0)
    transport: str = "auto",          # "auto" | "static" | "butterfly"
    wire: str = "auto",
    stage_cfgs=None,
) -> tuple:
    """Execute a :class:`~repro.agg.nested.NestedPlan` over a multi-axis
    mesh: stage s runs :func:`run_plan_segments_local` on ``axes[s]``.

    Must be called inside shard_map with **every** ``axes[s]`` manual.
    Stage 0 runs each cluster's intra tree concurrently over ``axes[0]``
    (cluster c = the rank group sharing the later-axis coordinates — the
    (pod, data) mesh's pod p holds clients ``p·K_d .. p·K_d+K_d−1``, so
    the plan must be mesh-aligned; checked while the plan is a host
    constant). Stage s ≥ 1 folds the previous stage's owned segment with
    weight 1 and that stage's EF tier over ``axes[s]`` — intra-stage
    ppermutes ride ``axes[0]`` (ICI), inter-stage ppermutes ``axes[1]``
    (DCI), exactly the two-stage hierarchical ring generalized to
    arbitrary per-stage trees.

    Per-pod trees may differ: the stage's clustered arrays travel as
    traced ``[C, L, W]`` leaves, each rank group selects its cluster's
    subplan by mesh index, and transport falls back to the ⌈log₂K⌉
    butterfly (identical clusters keep the static per-slot ppermute — the
    chain×chain nested plan reproduces the historic two-stage rotated
    ring, collective for collective). A ``TopologySchedule`` of nested
    plans therefore compiles to one specialization per padded nested
    shape.

    Returns ``(final segment [n // Πs K_s], new client EF [n],
    tuple of new stage-EF tiers, tuple of per-stage RingStats)``.
    """
    from repro.agg.nested import NestedPlan

    if not isinstance(nested, NestedPlan):
        raise TypeError(f"expected a NestedPlan, got {type(nested)!r}")
    n_stages = nested.num_stages
    axes = tuple(axes)
    if len(axes) != n_stages:
        raise ValueError(f"nested plan has {n_stages} stages but {len(axes)} "
                         f"axes were given")
    cfgs = list(stage_cfgs) if stage_cfgs is not None else [cfg] * n_stages
    if len(cfgs) != n_stages:
        raise ValueError(f"stage_cfgs has {len(cfgs)} entries for "
                         f"{n_stages} stages")
    stage_ef_local = tuple(stage_ef_local)
    if len(stage_ef_local) != n_stages - 1:
        raise ValueError(f"need {n_stages - 1} stage-EF slices, got "
                         f"{len(stage_ef_local)}")
    sizes = [jax.lax.axis_size(a) for a in axes]
    if nested.num_clients != int(np.prod(sizes)):
        raise ValueError(
            f"nested plan has {nested.num_clients} clients but the axes "
            f"{axes!r} provide {int(np.prod(sizes))} ranks")
    if transport not in ("auto", "static", "butterfly"):
        raise ValueError(f"unknown transport {transport!r}")

    # cluster index at stage s = the unit this rank group feeds at stage
    # s+1: u_s = u_{s+1}·K_s + r_s (client k = ... r_{S-1}·K_{S-2}·K_0 +
    # ... + r_0 — later axes are major, matching the (pod, data) dp order)
    cluster_at = [None] * n_stages
    u = jnp.int32(0)
    for s in range(n_stages - 1, -1, -1):
        cluster_at[s] = u
        u = u * sizes[s] + jax.lax.axis_index(axes[s]).astype(jnp.int32)

    cur = flat_local
    cur_mask = global_mask_local
    ef_new = None
    stage_ef_new = []
    stage_stats = []
    for s in range(n_stages):
        last = s == n_stages - 1
        if last:
            plan_s = nested.stages[s]
            tr_s = transport
        else:
            clustered = nested.clustered[s]
            if clustered.num_units != sizes[s]:
                raise ValueError(
                    f"stage {s} clusters have {clustered.num_units} members "
                    f"but axis {axes[s]!r} has {sizes[s]} ranks")
            aligned = clustered.mesh_aligned()
            if aligned is False:
                raise ValueError(
                    f"stage {s} clusters are not mesh-aligned (cluster c "
                    f"must be clients c·{sizes[s]}..c·{sizes[s]}+"
                    f"{sizes[s] - 1}); re-cluster or run on host")
            if transport != "butterfly" and clustered.uniform():
                plan_s = clustered.subplan(0)     # static numpy subplan
                tr_s = transport
            else:
                if transport == "static":
                    raise ValueError(
                        "transport='static' needs identical trace-time-"
                        "constant cluster plans; per-cluster trees route "
                        "through the butterfly")
                plan_s = jax.tree.map(jnp.asarray, clustered).subplan(
                    cluster_at[s])
                tr_s = "butterfly"
        w_s = weight if s == 0 else jnp.float32(1)
        p_s = participate if s == 0 else None
        ef_s = ef_local if s == 0 else stage_ef_local[s - 1]
        seg_out, ef_out, st = run_plan_segments_local(
            cfgs[s], plan_s, cur, ef_s, w_s, axis=axes[s],
            global_mask_local=cur_mask, participate=p_s, transport=tr_s,
            wire=wire)
        if s == 0:
            ef_new = ef_out
        else:
            stage_ef_new.append(ef_out)
        stage_stats.append(st)
        if not last and cur_mask is not None:
            seg = seg_out.shape[0]
            r_s = jax.lax.axis_index(axes[s])
            cur_mask = jax.lax.dynamic_slice(cur_mask, (r_s * seg,), (seg,))
        cur = seg_out
    return cur, ef_new, tuple(stage_ef_new), tuple(stage_stats)


def _pad_plan_clients(plan: AggPlan, k_new: int) -> AggPlan:
    """Grow a stage plan's client count to the mesh size for the
    client-per-rank kernel: the added clients never appear in the level
    schedule (their ranks simply never activate), only the dummy/sink/
    trash row ids shift. jnp ops throughout so traced schedule plans pad
    under jit."""
    k = plan.num_clients
    if k == k_new:
        return plan
    if k > k_new:
        raise ValueError(f"cannot shrink a plan from {k} to {k_new} clients")
    shift = k_new - k
    node_id = jnp.where(jnp.asarray(plan.node_id) == k, k_new,
                        jnp.asarray(plan.node_id))
    par = jnp.asarray(plan.parent_row)
    parent_row = jnp.where(par >= k, par + shift, par)
    pad1 = lambda a, v, dt: jnp.concatenate(
        [jnp.asarray(a, dt), jnp.full((shift,), v, dt)])
    return AggPlan(
        node_id=node_id.astype(jnp.int32),
        slot_mask=jnp.asarray(plan.slot_mask),
        parent_row=parent_row.astype(jnp.int32),
        flat_pos=pad1(plan.flat_pos, 0, jnp.int32),
        alive=pad1(plan.alive, 1.0, jnp.float32),
        q_budget=(None if plan.q_budget is None
                  else pad1(plan.q_budget, 0, jnp.int32)),
        num_clients=k_new, num_sinks=plan.num_sinks)


def execute_nested_sharded(
    cfg: AggConfig,
    nested,                        # NestedPlan
    grads: Array,                  # [K, d] per-client effective gradients
    e: Array,                      # [K, d] client-tier EF memory
    weights: Array,                # [K]    D_k
    *,
    mesh=None,
    stage_e=None,                  # EF tiers for stages ≥ 1 ([K_s, d])
    global_mask: Optional[Array] = None,
    participate: Optional[Array] = None,
    wire: str = "auto",
    stage_cfgs=None,
):
    """One staged round on a client-per-rank mesh — drop-in for host
    :func:`repro.agg.nested.execute_nested` (same ``NestedResult``
    contract, bit-exact per stage: every stage runs
    :func:`run_plan_clients_local`, upper stages on the same mesh with the
    previous stage's replicated sink partials as rank-local gradients —
    ranks beyond a stage's unit count never activate)."""
    from repro.agg.nested import NestedPlan, NestedResult, zero_stage_ef

    if not isinstance(nested, NestedPlan):
        raise TypeError(f"expected a NestedPlan, got {type(nested)!r}")
    k, d = grads.shape
    if nested.num_clients != k:
        raise ValueError(f"nested plan has {nested.num_clients} clients, "
                         f"grads {k}")
    n_stages = nested.num_stages
    cfgs = list(stage_cfgs) if stage_cfgs is not None else [cfg] * n_stages
    if mesh is None:
        mesh = client_mesh(k)
    axis = mesh.axis_names[0]
    from jax.sharding import PartitionSpec as P

    if stage_e is None:
        stage_e = zero_stage_ef(nested, d, grads.dtype)
    stage_e = tuple(stage_e)
    units = nested.stage_units
    # stage EF tiers ride through the mesh padded to one row per rank
    stage_e_pad = tuple(
        jnp.concatenate([se, jnp.zeros((k - units[s + 1],) + se.shape[1:],
                                       se.dtype)])
        if units[s + 1] < k else se
        for s, se in enumerate(stage_e))

    has_part = participate is not None
    part = (jnp.ones((k,), grads.dtype) if participate is None
            else participate)
    gmask = (jnp.zeros((d,), grads.dtype) if global_mask is None
             else global_mask)

    def stage_wire(s, plan):
        use = _use_compact(cfgs[s], d, plan, has_part and s == 0, wire)
        return ("compact" if use and (wire == "compact"
                or jnp.dtype(cfgs[s].wire_dtype) == jnp.float32)
                else "dense")

    wires = [stage_wire(s, nested.stages[s]) for s in range(n_stages)]

    def body(nested, g_l, e_l, w_l, se_l, part_l, gm):
        r = jax.lax.axis_index(axis)
        agg, e_new, st0 = run_plan_clients_local(
            cfgs[0], nested.stages[0], g_l[0], e_l[0], w_l[0], axis=axis,
            global_mask=gm, participate=part_l[0] if has_part else None,
            wire=wires[0])
        prev = agg if nested.stages[0].num_sinks > 1 else agg[None]
        se_new, st_up = [], []
        for s in range(1, n_stages):
            c = units[s]
            plan_s = _pad_plan_clients(nested.stages[s], k)
            g_s = jnp.where(r < c, prev[jnp.clip(r, 0, c - 1)],
                            jnp.zeros((d,), prev.dtype))
            agg, e_s, st_s = run_plan_clients_local(
                cfgs[s], plan_s, g_s, se_l[s - 1][0], jnp.float32(1),
                axis=axis, global_mask=gm, wire=wires[s])
            prev = agg if plan_s.num_sinks > 1 else agg[None]
            se_new.append(e_s[None])
            st_up.append(jax.tree.map(lambda x: x[None], st_s))
        return (prev[0], e_new[None], tuple(se_new),
                jax.tree.map(lambda x: x[None], st0), tuple(st_up))

    nested_specs = jax.tree.map(lambda _: P(), nested)
    stats_spec = jax.tree.map(lambda _: P(axis), HopStats(0, 0, 0, 0., 0.))
    agg, e_new, se_new, st0, st_up = compat.shard_map(
        body, mesh=mesh,
        in_specs=(nested_specs, P(axis), P(axis), P(axis),
                  tuple(P(axis) for _ in stage_e_pad), P(axis), P()),
        out_specs=(P(), P(axis), tuple(P(axis) for _ in stage_e_pad),
                   stats_spec, tuple(stats_spec for _ in stage_e_pad)),
        axis_names={axis},
    )(nested, grads, e, weights, stage_e_pad, part, gmask)
    # drop the rank-padding rows of the upper tiers
    se_new = tuple(se[:units[s + 1]] for s, se in enumerate(se_new))
    st_up = tuple(jax.tree.map(lambda x: x[:units[s + 1]], st)
                  for s, st in enumerate(st_up))
    return NestedResult(aggregate=agg, e_new=e_new, stage_e_new=se_new,
                        stats=st0, stage_stats=st_up)
