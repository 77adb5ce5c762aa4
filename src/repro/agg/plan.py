"""Topology-polymorphic aggregation: ``compile_plan`` + one ``execute``.

Every aggregation topology the repo knows — the paper's linear chain, a
permuted/healed chain order, or a routed constellation :class:`AggTree` —
lowers to one canonical representation, the :class:`AggPlan`: a padded
``(L, W)`` level schedule (L levels run sequentially, up to W nodes per
level run concurrently). ``execute(cfg, plan, ...)`` is the single round
entry point; it is bit-exact to :func:`repro.core.chain.run_chain` on
chain plans and subsumes :func:`repro.topo.tree.run_tree` (which now
delegates here).

The plan's arrays are *traced* jit arguments, not Python constants, so the
compiled round is specialized only on the padded ``(L, W)`` shape — every
topology padded to the same shape shares one XLA executable. That is what
makes time-varying topologies (:class:`repro.agg.schedule.TopologySchedule`)
cheap: a round-per-graph LEO schedule re-routes continuously but triggers
exactly one trace.

Plans optionally carry per-client ``q_budget`` (int32 [K]) — the
bandwidth-aware Top-Q budgets of :func:`bandwidth_budgets`, where narrow
uplinks get proportionally smaller local budgets. Without a budget the node
steps run the paper's static-``q`` exact Top-Q, bit-identical to before.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.algorithms import (AggConfig, AggKind, HopStats, level_step,
                                   level_step_batched)
from repro.obs import scopes
from repro.topo.tree import PS, AggTree, build_schedule, path_tree

Array = jax.Array

Topology = Union[int, AggTree, Sequence, np.ndarray]


@dataclasses.dataclass(frozen=True)
class AggPlan:
    """Canonical padded level schedule — the compiled form of a topology.

    ``node_id[l, w]`` is the client run in slot w of level l, deepest level
    first (padding slots hold K, a zero dummy row); ``slot_mask`` is 1.0 for
    real slots; ``parent_row[l, w]`` is the inbox row receiving that slot's
    γ (client index, K..K+R−1 for the R sink rows, K+R trash row for
    padding — single-sink plans have R = 1 and their sink K *is* the PS,
    exactly the historic layout); ``flat_pos[k]`` maps client k back out of
    schedule order. ``alive[k]`` is 0.0 for stranded stubs (clients routing
    could not reach) — folded into ``participate`` by :func:`execute`.
    ``q_budget`` (optional int32 [K]) carries per-client local Top-Q
    budgets.

    ``num_sinks`` > 1 makes the plan a *forest*: R independent trees whose
    roots deliver to distinct sink rows — the stage form of a
    :class:`repro.agg.nested.NestedPlan`, where stage s's sink c feeds
    stage s+1's client c.

    Registered as a jax pytree: arrays are leaves (traced jit arguments),
    ``num_clients``/``num_sinks`` are static. Two plans with the same
    ``(L, W)``, sink count and leaf dtypes therefore share one jit
    specialization.
    """

    node_id: np.ndarray       # [L, W] int32
    slot_mask: np.ndarray     # [L, W] float32
    parent_row: np.ndarray    # [L, W] int32
    flat_pos: np.ndarray      # [K] int32
    alive: np.ndarray         # [K] float32
    q_budget: Optional[np.ndarray] = None   # [K] int32
    num_clients: int = 0
    num_sinks: int = 1

    @property
    def shape(self) -> tuple:
        """The padded ``(L, W)`` — the jit-specialization key."""
        return tuple(self.node_id.shape)

    def pad(self, shape: tuple) -> "AggPlan":
        """Re-pad to a larger ``(L, W)`` (bit-exact: padding slots are
        no-ops — they run the zero dummy row and scatter into the trash
        row)."""
        big_l, big_w = shape
        l, w = self.shape
        if (big_l, big_w) == (l, w):
            return self
        if big_l < l or big_w < w:
            raise ValueError(f"cannot shrink plan {self.shape} to {shape}")
        k = self.num_clients
        node_id = np.full((big_l, big_w), k, np.int32)
        slot_mask = np.zeros((big_l, big_w), np.float32)
        parent_row = np.full((big_l, big_w), k + self.num_sinks, np.int32)
        node_id[:l, :w] = self.node_id
        slot_mask[:l, :w] = self.slot_mask
        parent_row[:l, :w] = self.parent_row
        li, wi = np.divmod(np.asarray(self.flat_pos, np.int64), w)
        flat_pos = (li * big_w + wi).astype(np.int32)
        return AggPlan(node_id=node_id, slot_mask=slot_mask,
                       parent_row=parent_row, flat_pos=flat_pos,
                       alive=self.alive, q_budget=self.q_budget,
                       num_clients=k, num_sinks=self.num_sinks)


def _plan_flatten(p: AggPlan):
    return ((p.node_id, p.slot_mask, p.parent_row, p.flat_pos, p.alive,
             p.q_budget), (p.num_clients, p.num_sinks))


def _plan_unflatten(aux, leaves):
    num_clients, num_sinks = aux
    node_id, slot_mask, parent_row, flat_pos, alive, q_budget = leaves
    return AggPlan(node_id=node_id, slot_mask=slot_mask,
                   parent_row=parent_row, flat_pos=flat_pos, alive=alive,
                   q_budget=q_budget, num_clients=num_clients,
                   num_sinks=num_sinks)


jax.tree_util.register_pytree_node(AggPlan, _plan_flatten, _plan_unflatten)


# ---------------------------------------------------------------------------
# compile_plan
# ---------------------------------------------------------------------------

def _order_to_tree(order: np.ndarray, num_clients: Optional[int]) -> AggTree:
    """A (possibly permuted) chain order → the equivalent path tree.

    ``order[0]`` is the client adjacent to the PS; ``order[-1]`` the far
    end (matching ``run_chain_with_topology``). Must be a full permutation —
    express exclusions via ``participate`` or by routing a tree.
    """
    order = np.asarray(order, np.int64).reshape(-1)
    k = num_clients if num_clients is not None else len(order)
    if sorted(order.tolist()) != list(range(k)):
        raise ValueError(
            f"chain order must be a permutation of 0..{k - 1}; got "
            f"{order.tolist()} (exclude nodes via participate, not order)")
    parent = np.empty((k,), np.int64)
    parent[order[0]] = PS
    parent[order[1:]] = order[:-1]
    return AggTree(parent=tuple(int(p) for p in parent))


def as_tree(topology: Topology, num_clients: Optional[int] = None) -> AggTree:
    """Coerce any supported topology description to an :class:`AggTree`.

    * ``int K`` — the paper's identity chain over K clients;
    * :class:`AggTree` — used as-is;
    * 1-D int sequence — a (healed/permuted) chain visiting order;
    * anything with a ``.tree()`` method (``repro.fed.topology``'s
      ``TreeTopology``) or a ``ConstellationGraph`` — routed via the
      shortest-path policy.
    """
    if isinstance(topology, AggTree):
        return topology
    if isinstance(topology, int):
        return path_tree(topology)
    if hasattr(topology, "tree") and callable(topology.tree):
        return topology.tree()
    if hasattr(topology, "client_nodes"):         # ConstellationGraph
        from repro.topo.routing import shortest_path_tree
        return shortest_path_tree(topology)
    if hasattr(topology, "order") and callable(topology.order):
        return _order_to_tree(np.asarray(topology.order()), num_clients)
    return _order_to_tree(np.asarray(topology), num_clients)


def compile_plan(topology: Topology, *,
                 num_clients: Optional[int] = None,
                 pad_to: Optional[tuple] = None,
                 q_budget: Optional[np.ndarray] = None) -> AggPlan:
    """Lower a topology to its canonical :class:`AggPlan`.

    ``pad_to=(L, W)`` pads the level schedule so plans from different
    topologies share one jit specialization (see
    :class:`repro.agg.schedule.TopologySchedule`). ``q_budget`` attaches
    per-client local Top-Q budgets (:func:`bandwidth_budgets`).
    """
    tree = as_tree(topology, num_clients)
    k = tree.num_clients
    sched = build_schedule(tree)
    alive = (np.ones((k,), np.float32) if tree.reachable is None
             else np.asarray(tree.reachable, np.float32))
    qb = None
    if q_budget is not None:
        qb = np.asarray(q_budget, np.int32).reshape(-1)
        if qb.shape != (k,):
            raise ValueError(f"q_budget must be [K={k}]; got {qb.shape}")
    plan = AggPlan(node_id=np.asarray(sched.node_id, np.int32),
                   slot_mask=np.asarray(sched.slot_mask, np.float32),
                   parent_row=np.asarray(sched.parent_row, np.int32),
                   flat_pos=np.asarray(sched.flat_pos, np.int32),
                   alive=alive, q_budget=qb, num_clients=k)
    if pad_to is not None:
        plan = plan.pad(tuple(pad_to))
    return plan


# ---------------------------------------------------------------------------
# Bandwidth-aware budgets
# ---------------------------------------------------------------------------

def bandwidth_budgets(cfg: AggConfig, tree: AggTree, *,
                      floor: int = 1) -> np.ndarray:
    """Per-client local Top-Q budgets scaled by uplink bandwidth.

    ``q_k = max(floor, round(q_base · bw_k / max bw))`` where ``q_base`` is
    the algorithm's local budget (``q``, or ``q_local`` for the TC
    variants). Narrow uplinks transmit fewer nonzeros, so total §V bits
    drop versus the uniform budget on any heterogeneous-bandwidth tree
    (zero-bandwidth stubs get the floor; they never transmit anyway).
    """
    if tree.uplink_bw_bps is None:
        raise ValueError("tree has no per-link bandwidth (built by hand?) — "
                         "route it from a ConstellationGraph")
    bw = np.asarray(tree.uplink_bw_bps, np.float64)
    base = (cfg.q_local if cfg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA)
            else cfg.q)
    pos = bw[bw > 0]
    if pos.size == 0:
        return np.full((tree.num_clients,), floor, np.int32)
    scaled = np.round(base * bw / pos.max())
    return np.where(bw > 0, np.maximum(floor, scaled),
                    floor).astype(np.int32)


# ---------------------------------------------------------------------------
# execute — the single round entry point
# ---------------------------------------------------------------------------

class RoundResult(NamedTuple):
    aggregate: Array      # what the PS receives (Σ over its children), [d];
                          # forest plans (num_sinks R > 1) get [R, d] — one
                          # partial aggregate per sink, in sink order
    e_new: Array          # updated EF memory, [K, d] (client index order)
    stats: HopStats       # per-hop stats, leaves [K] (client index order)


def _take_rows(x: Array, ids: Array) -> Array:
    """``x[ids]`` for a [R, d] array and a [n] index vector, as n row slices.

    The TPU compiler splits a gather of whole rows into d/32768 unrolled
    pieces (512 at d=2^24), which costs seconds of compile time per
    gather; a dynamic row slice is one copy."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(x, ids[i], keepdims=False)
                      for i in range(ids.shape[0])])


@jax.named_scope(scopes.EXECUTOR)
def execute(
    cfg: AggConfig,
    plan: AggPlan,
    grads: Array,                  # [K, d] per-client effective gradients g_k
    e: Array,                      # [K, d] EF memory
    weights: Array,                # [K]    D_k
    *,
    global_mask: Optional[Array] = None,   # [d] TCS mask m^t (TC algorithms)
    participate: Optional[Array] = None,   # [K] 0/1 straggler mask
) -> RoundResult:
    """One aggregation round over a compiled plan (any topology).

    Same contract as :func:`repro.core.chain.run_chain` with the topology
    factored into ``plan``; bit-exact to ``run_chain`` on chain plans and
    invariant under padding. A ``lax.scan`` walks the L levels deepest
    first while :func:`repro.core.algorithms.level_step` runs every node
    of a level concurrently — the historic ``vmap`` of the scalar node
    step off-TPU, one batched Pallas call per level when the fused kernel
    path is on; children's partial aggregates merge at each parent via a
    masked scatter-add (padding slots run the zero dummy row and target
    the trash row, so they are no-ops).
    """
    k, d = grads.shape
    if plan.num_clients != k:
        raise ValueError(f"plan has {plan.num_clients} clients, grads {k}")
    if global_mask is None:
        global_mask = jnp.zeros((d,), grads.dtype)
    if participate is None:
        participate = jnp.ones((k,), grads.dtype)
    participate = participate * jnp.asarray(plan.alive, grads.dtype)
    lvl = level_step(cfg)

    # one zero dummy row (index K) backs the padding slots
    zrow = jnp.zeros((1, d), grads.dtype)
    g_ext = jnp.concatenate([grads, zrow])
    e_ext = jnp.concatenate([e, zrow])
    w_ext = jnp.concatenate([weights, jnp.zeros((1,), weights.dtype)])
    p_ext = jnp.concatenate(
        [participate, jnp.zeros((1,), participate.dtype)])
    q_ext = None
    if plan.q_budget is not None:
        q_ext = jnp.concatenate([jnp.asarray(plan.q_budget, jnp.int32),
                                 jnp.zeros((1,), jnp.int32)])

    def body(inbox, xs):
        ids, mask, par = xs
        gamma_out, e_new, stats = lvl(
            _take_rows(g_ext, ids), _take_rows(inbox, ids),
            _take_rows(e_ext, ids), w_ext[ids], p_ext[ids],
            global_mask, None if q_ext is None else q_ext[ids], mask)
        inbox = inbox.at[par].add(gamma_out * mask[:, None])
        return inbox, (e_new, stats)

    # inbox rows: 0..K−1 per-client incoming sums, K..K+R−1 the sink rows
    # (R = 1: the PS), K+R = trash
    r_sinks = plan.num_sinks
    inbox0 = jnp.zeros((k + r_sinks + 1, d), grads.dtype)
    inbox, (e_lvl, st_lvl) = jax.lax.scan(
        body, inbox0,
        (jnp.asarray(plan.node_id), jnp.asarray(plan.slot_mask),
         jnp.asarray(plan.parent_row)))

    # scan outputs are [L, W, ...] in schedule order → client index order
    pos = jnp.asarray(plan.flat_pos)
    e_new = _take_rows(e_lvl.reshape(-1, d), pos)
    stats = jax.tree.map(
        lambda s: s.reshape((-1,) + s.shape[2:])[pos], st_lvl)
    agg = inbox[k] if r_sinks == 1 else inbox[k:k + r_sinks]
    return RoundResult(aggregate=agg, e_new=e_new, stats=stats)


# ---------------------------------------------------------------------------
# execute_batched — B cohorts per launch (multi-tenant rounds)
# ---------------------------------------------------------------------------

def stack_plans(plans: Sequence[AggPlan]) -> AggPlan:
    """Stack B shape-identical plans into one cohort-batched plan.

    The result's array leaves carry a leading cohort axis ``[B, ...]``
    (still traced jit args — B plans with one ``(L, W)`` shape share one
    specialization of :func:`execute_batched`). Plans must agree on shape,
    client count, sink count and ``q_budget`` presence — pad heterogeneous
    plans to a common shape first (:func:`repro.agg.schedule.common_shape`;
    the bucket packing of :class:`repro.agg.batching.RoundScheduler` does
    this for you). A stacked plan is only consumable by the batched
    executors; ``pad`` it *before* stacking.
    """
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    p0 = plans[0]
    for p in plans[1:]:
        if p.shape != p0.shape:
            raise ValueError(f"plan shapes differ: {p.shape} vs {p0.shape} "
                             f"(pad to a common shape first)")
        if (p.num_clients, p.num_sinks) != (p0.num_clients, p0.num_sinks):
            raise ValueError("stacked plans must share client/sink counts")
        if (p.q_budget is None) != (p0.q_budget is None):
            raise ValueError("stacked plans must agree on q_budget presence")
    stk = lambda leaf: np.stack([np.asarray(getattr(p, leaf))
                                 for p in plans])
    return AggPlan(node_id=stk("node_id"), slot_mask=stk("slot_mask"),
                   parent_row=stk("parent_row"), flat_pos=stk("flat_pos"),
                   alive=stk("alive"),
                   q_budget=None if p0.q_budget is None else stk("q_budget"),
                   num_clients=p0.num_clients, num_sinks=p0.num_sinks)


@jax.named_scope(scopes.EXECUTOR)
def execute_batched(
    cfg: AggConfig,
    plan: AggPlan,
    grads: Array,                  # [B, K, d] per-cohort client gradients
    e: Array,                      # [B, K, d] per-cohort EF memory
    weights: Array,                # [B, K]
    *,
    global_mask: Optional[Array] = None,   # [B, d] per-cohort TCS masks
    participate: Optional[Array] = None,   # [B, K] per-cohort stragglers
) -> RoundResult:
    """B independent aggregation rounds in one launch.

    ``plan`` is either a single plan shared by every cohort (leaves
    ``[L, W]``) or a :func:`stack_plans` batch of B shape-identical plans
    (leaves ``[B, L, W]`` — heterogeneous topologies in one bucket). The
    levels run through :func:`level_step_batched`, which flattens the B
    cohorts cohort-major into one ``level_step`` launch — a single
    ``pallas_call`` per kernel stage on the fused path, instead of B.

    Every cohort's math is independent (gathers, row-parallel lanes, and a
    per-cohort scatter-add identical to :func:`execute`'s), so the result
    leaves ``[B, ...]`` are bitwise identical, per cohort, to B sequential
    ``execute`` calls — the multi-tenant contract pinned by
    tests/test_batched_rounds.py in interpret mode. One caveat: on
    *stacked* plans the per-cohort index gathers lower through
    ``take_along_axis``, and XLA may fuse the ``err_sq`` ‖e‖² reduction
    with a different association than the sequential executor — the
    aggregate, EF rows, and integer-valued §V counters (``nnz*``,
    ``bits``) stay bitwise, but ``err_sq`` is only reproduced to float
    summation order (≲1 ulp).
    """
    b, k, d = grads.shape
    if plan.num_clients != k:
        raise ValueError(f"plan has {plan.num_clients} clients, grads {k}")
    stacked = np.ndim(plan.node_id) == 3
    if stacked and plan.node_id.shape[0] != b:
        raise ValueError(f"stacked plan has {plan.node_id.shape[0]} "
                         f"cohorts, grads {b}")
    if global_mask is None:
        global_mask = jnp.zeros((b, d), grads.dtype)
    if participate is None:
        participate = jnp.ones((b, k), grads.dtype)
    participate = participate * jnp.asarray(plan.alive, grads.dtype)
    lvl = level_step_batched(cfg)

    zrow = jnp.zeros((b, 1, d), grads.dtype)
    g_ext = jnp.concatenate([grads, zrow], axis=1)
    e_ext = jnp.concatenate([e, zrow], axis=1)
    w_ext = jnp.concatenate(
        [weights, jnp.zeros((b, 1), weights.dtype)], axis=1)
    p_ext = jnp.concatenate(
        [participate, jnp.zeros((b, 1), participate.dtype)], axis=1)
    q_ext = None
    if plan.q_budget is not None:
        qb = jnp.asarray(plan.q_budget, jnp.int32)
        if not stacked and qb.ndim == 1:
            qb = jnp.broadcast_to(qb[None], (b, k))
        q_ext = jnp.concatenate([qb, jnp.zeros((b, 1), jnp.int32)], axis=1)

    def take_rows(x, ids):
        # ids [W] (shared plan) or [B, W] (stacked): per-cohort row gather
        if ids.ndim == 1:
            return x[:, ids]
        idx = ids.reshape(ids.shape + (1,) * (x.ndim - 2))
        return jnp.take_along_axis(x, idx, axis=1)

    def body(inbox, xs):
        ids, mask, par = xs
        mask_b = (mask if mask.ndim == 2
                  else jnp.broadcast_to(mask, (b,) + mask.shape))
        gamma_out, e_new, stats = lvl(
            take_rows(g_ext, ids), take_rows(inbox, ids),
            take_rows(e_ext, ids), take_rows(w_ext, ids),
            take_rows(p_ext, ids), global_mask,
            None if q_ext is None else take_rows(q_ext, ids), mask_b)
        scatter = lambda ib, go, pr, mk: ib.at[pr].add(go * mk[:, None])
        par_ax = 0 if par.ndim == 2 else None
        inbox = jax.vmap(scatter, in_axes=(0, 0, par_ax, 0))(
            inbox, gamma_out, par, mask_b)
        return inbox, (e_new, stats)

    r_sinks = plan.num_sinks
    lead = lambda x: (jnp.moveaxis(jnp.asarray(x), 1, 0) if stacked
                      else jnp.asarray(x))
    inbox0 = jnp.zeros((b, k + r_sinks + 1, d), grads.dtype)
    inbox, (e_lvl, st_lvl) = jax.lax.scan(
        body, inbox0,
        (lead(plan.node_id), lead(plan.slot_mask), lead(plan.parent_row)))

    # scan outputs are [L, B, W, ...] → cohort-major [B, L*W, ...] →
    # per-cohort client index order via flat_pos
    pos = jnp.asarray(plan.flat_pos)

    def reorder(x):
        flat = jnp.moveaxis(x, 1, 0).reshape((b, -1) + x.shape[3:])
        if pos.ndim == 1:
            return flat[:, pos]
        idx = pos.reshape(pos.shape + (1,) * (flat.ndim - 2))
        return jnp.take_along_axis(flat, idx, axis=1)

    e_new = reorder(e_lvl)
    stats = jax.tree.map(reorder, st_lvl)
    agg = inbox[:, k] if r_sinks == 1 else inbox[:, k:k + r_sinks]
    return RoundResult(aggregate=agg, e_new=e_new, stats=stats)
