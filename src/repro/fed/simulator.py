"""Multi-hop FL simulator — the paper's §VI experiment engine.

K clients train a d=7850 logistic-regression model on (synthetic-)MNIST.
Per round:

  1. every client takes one SGD step on its local minibatch → effective
     gradient g_k = w_k − w  (= −lr·∇_k);
  2. the round's aggregation topology — chain, permuted chain, or routed
     constellation tree, compiled to an :class:`repro.agg.AggPlan` —
     aggregates {D_k·g_k} with the configured Algorithm 1–5 (error feedback
     persists across rounds);
  3. the PS applies w ← w + γ_1 / D and broadcasts.

The round is ONE jitted function for every topology: the plan's arrays are
traced arguments, so switching topologies per round (healed chains via
``order_fn``, relay deaths via ``failure_schedule``, LEO re-routing via
``topology_schedule``) re-traces only when the padded ``(L, W)`` schedule
shape grows — plans padded to a common shape share the executable.

``backend="device"`` runs the same rounds through the device-plan lowering
(:func:`repro.agg.device.execute_sharded`): one local device per client
(``XLA_FLAGS=--xla_force_host_platform_device_count=K`` fakes them on
CPU), levels in lockstep over the mesh, compact wire transport. The
lowered round is bit-exact to host ``execute`` on identical inputs
(tested in tests/test_device_plan.py); whole training *trajectories* agree
to float tolerance only, because XLA fuses the (identical) gradient math
differently when a shard_map consumes it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.agg import (AggPlan, NestedPlan, TopologySchedule, compile_nested,
                       compile_plan, execute, execute_batched, execute_nested,
                       zero_stage_ef)
from repro.configs.paper_mnist import PaperConfig
from repro.core import tcs as tcs_mod
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import FederatedData, client_minibatch
from repro.fed.topology import FailureSchedule, TreeTopology
from repro.obs import scopes
from repro.obs.collector import RoundBuffer, TraceCounter
from repro.obs.timing import PhaseTimer
from repro.runtime.fault import banked_mass, dead_banked_mass

Array = jax.Array


# ---------------------------------------------------------------------------
# Logistic-regression model (w: [784,10], b: [10] — d = 7850)
# ---------------------------------------------------------------------------

def lr_init(pc: PaperConfig) -> dict:
    return {"w": jnp.zeros((pc.input_dim, pc.num_classes), jnp.float32),
            "b": jnp.zeros((pc.num_classes,), jnp.float32)}


def lr_loss(params: dict, x: Array, y: Array) -> Array:
    logits = x @ params["w"] + params["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def lr_accuracy(params: dict, x: Array, y: Array) -> Array:
    logits = x @ params["w"] + params["b"]
    return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))


def flatten_lr(params: dict) -> Array:
    return jnp.concatenate([params["w"].reshape(-1), params["b"]])


def unflatten_lr(flat: Array, pc: PaperConfig) -> dict:
    wd = pc.input_dim * pc.num_classes
    return {"w": flat[:wd].reshape(pc.input_dim, pc.num_classes),
            "b": flat[wd:wd + pc.num_classes]}


# ---------------------------------------------------------------------------
# Simulator
# ---------------------------------------------------------------------------

class SimState(NamedTuple):
    round: Array            # int32
    flat_w: Array           # [d] global model
    ef: Array               # [K, d] error feedback
    tcs_prev: Array         # [d] w^{t-1} (used by TC algorithms)
    rng: Array
    stage_ef: tuple = ()    # upper EF tiers ([K_s, d]) of a nested topology


class RoundLog(NamedTuple):
    """Per-round telemetry — everything the jitted round already computes.

    Leaves stay on device until the history buffer flushes (one
    ``device_get`` per flush — see :meth:`Simulator.run`). ``stats`` holds
    the per-stage :class:`~repro.core.algorithms.HopStats` (stage 0 = the
    client forest, leaves ``[K_s]`` in client index order); the scalar
    curves the simulator returns (loss/bits/nnz) reduce from these on the
    host, and the trace collector consumes them verbatim.
    """

    loss: Array
    stats: tuple            # per-stage HopStats (§V exact per-hop bits)
    participation: Array    # [K] effective mask (participate ∧ alive)
    ef_mass: Array          # [K] ‖e_k‖₁ banked after this round
    stage_ef_mass: tuple    # banked mass per upper EF tier ([K_s] each)
    ef_dead_mass: Array     # Σ over non-participants of ‖e_k‖₁ (‖e_dead‖)


def _fetch_logs(buffer: RoundBuffer) -> list:
    """The run loop's single device→host sync point: materialize every
    buffered round log with one ``device_get``. Module-level so tests can
    monkeypatch it to count syncs."""
    return buffer.flush()


class _PlanCache:
    """Host-side plan store keyed by topology identity, re-padded in place.

    All cached plans share one ``(L, W)`` (the running elementwise max), so
    the jitted round retraces only when a new topology *grows* the schedule
    shape — never when flipping between already-seen topologies.
    """

    def __init__(self, num_clients: int):
        self.k = num_clients
        self._plans: dict = {}
        self._raws: dict = {}
        self._shape: Optional[tuple] = None

    def raw(self, key):
        """The topology object ``build()`` returned (an AggTree in tree
        mode — it carries the link model the trace timeline uses)."""
        return self._raws.get(key)

    def get(self, key, build: Callable[[], Any]) -> AggPlan:
        plan = self._plans.get(key)
        if plan is None:
            raw = build()
            self._raws[key] = raw
            plan = compile_plan(raw, num_clients=self.k)
            shape = (plan.shape if self._shape is None else
                     (max(self._shape[0], plan.shape[0]),
                      max(self._shape[1], plan.shape[1])))
            self._shape = shape
            self._plans[key] = plan
            # a growing shape re-pads everything already cached so the whole
            # cache keeps sharing one specialization
            self._plans = {kk: pp.pad(shape)
                           for kk, pp in self._plans.items()}
        return self._plans[key]


@dataclasses.dataclass
class Simulator:
    """Multi-hop FL simulator over any aggregation topology.

    The default topology is the paper's identity chain. ``tree_topology``
    routes a constellation graph instead; relay deaths from a
    ``failure_schedule`` passed to :meth:`run` re-route the tree (re-rooting
    the severed subtree through surviving ISLs). Every topology goes through
    ``repro.agg.compile_plan`` into one shared jitted round — per-dead-set
    recompiles of the old engine collapse into a host-side plan lookup.
    """

    pc: PaperConfig
    agg: AggConfig
    fed: FederatedData
    local_lr: float = 0.1
    tree_topology: Optional[TreeTopology] = None
    # staged aggregation: a NestedPlan, a routed NestedTopology
    # (repro.topo.routing.cluster_routed), or a compile_nested stage spec —
    # rounds run execute_nested (host) / execute_nested_sharded (device),
    # the upper EF tiers persist in SimState.stage_ef
    nested_topology: Optional[Any] = None
    # "host": repro.agg.execute (single-device reference);
    # "device": repro.agg.device.execute_sharded — the plan lowered onto a
    # one-device-per-client shard_map mesh, bit-exact to "host".
    backend: str = "host"

    def __post_init__(self):
        self.k = self.fed.num_clients
        self.d = self.pc.d
        # D_k = per-round contribution weight (uniform minibatches → B each;
        # weights normalized at the PS by D = Σ D_k)
        self.weights = jnp.full((self.k,), 1.0, jnp.float32)
        if self.backend not in ("host", "device"):
            raise ValueError(f"unknown backend {self.backend!r}")
        self._nested = None
        if self.nested_topology is not None:
            if self.tree_topology is not None:
                raise ValueError("pass either tree_topology or "
                                 "nested_topology, not both")
            self._nested = (self.nested_topology
                            if isinstance(self.nested_topology, NestedPlan)
                            else compile_nested(self.nested_topology,
                                                num_clients=self.k))
            if self._nested.num_clients != self.k:
                raise ValueError(
                    f"nested topology has {self._nested.num_clients} "
                    f"clients, data has {self.k}")
        self._mesh = None
        if self.backend == "device":
            from repro.agg.device import client_mesh
            self._mesh = client_mesh(self.k)
        # counts jit specializations of the round closure: bumped at trace
        # time only, so attaching/detaching a trace collector provably
        # cannot add a retrace (tested in tests/test_obs.py)
        self.trace_counter = TraceCounter()

    def init(self, seed: int = 0) -> SimState:
        flat = flatten_lr(lr_init(self.pc))
        stage_ef = (() if self._nested is None
                    else zero_stage_ef(self._nested, self.d))
        return self._place(SimState(
            round=jnp.int32(0), flat_w=flat,
            ef=jnp.zeros((self.k, self.d), jnp.float32), tcs_prev=flat,
            rng=jax.random.PRNGKey(seed), stage_ef=stage_ef))

    def _state_shardings(self, state: SimState) -> SimState:
        """Device-backend state layout: EF rows over the client axis (the
        axis before d), everything else replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._mesh
        rep = NamedSharding(mesh, P())
        lead = (None,) * (state.ef.ndim - 2)        # the cohort axis, if any
        return jax.tree.map(lambda _: rep, state)._replace(
            ef=NamedSharding(mesh, P(*lead, mesh.axis_names[0])))

    def _place(self, state: SimState) -> SimState:
        """Commit a host-built state to the device round's state layout.

        The device round constrains its output state to the same layout
        (:meth:`_pin`), so round 1 and every later round hit one jit
        specialization instead of compiling again on round 2."""
        if self._mesh is None:
            return state
        return jax.device_put(state, self._state_shardings(state))

    def _pin(self, state: SimState) -> SimState:
        if self._mesh is None:
            return state
        return jax.lax.with_sharding_constraint(
            state, self._state_shardings(state))

    # -- one jitted round ---------------------------------------------------
    def round_fn(self) -> Callable:
        """One-round closure ``(state, plan, participate) -> (state, log)``.

        Topology-polymorphic: the plan is a traced argument, so one jit of
        this closure serves chains, healed chains, and routed trees alike.
        """
        pc, agg_cfg, k = self.pc, self.agg, self.k
        fed, weights, lr = self.fed, self.weights, self.local_lr
        needs_tcs = agg_cfg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA)
        mesh = self._mesh
        if mesh is None:
            run_round = execute
            run_nested = execute_nested
        else:
            from repro.agg.device import (execute_nested_sharded,
                                          execute_sharded)

            def run_round(cfg, plan, g, e, w, *, global_mask=None,
                          participate=None):
                return execute_sharded(cfg, plan, g, e, w, mesh=mesh,
                                       global_mask=global_mask,
                                       participate=participate)

            def run_nested(cfg, plan, g, e, w, *, stage_e, global_mask=None,
                           participate=None):
                return execute_nested_sharded(
                    cfg, plan, g, e, w, mesh=mesh, stage_e=stage_e,
                    global_mask=global_mask, participate=participate)

        trace_counter = self.trace_counter

        def one_round(state: SimState, plan: AggPlan,
                      participate: Optional[Array] = None):
            trace_counter.bump()        # runs at trace time only
            with jax.named_scope(scopes.GRADS):
                rng, kb = jax.random.split(state.rng)
                params = unflatten_lr(state.flat_w, pc)
                bx, by = client_minibatch(fed, kb, pc.batch_size)

                # local SGD step per client → effective gradients
                def client_grad(x, y):
                    g = jax.grad(lr_loss)(params, x, y)
                    return -lr * flatten_lr(g)          # g_k = w_k − w

                g = jax.vmap(client_grad)(bx, by)        # [K, d]

            with jax.named_scope(scopes.AGGREGATE):
                global_mask = None
                tcs_prev = state.tcs_prev
                if needs_tcs:
                    global_mask = tcs_mod.global_mask(
                        tcs_mod.TCSState(tcs_prev), state.flat_w,
                        agg_cfg.q_global)
                    tcs_prev = state.flat_w

                nested = isinstance(plan, NestedPlan)
                if nested:
                    res = run_nested(agg_cfg, plan, g, state.ef, weights,
                                     stage_e=state.stage_ef,
                                     global_mask=global_mask,
                                     participate=participate)
                    stage_ef = res.stage_e_new
                    all_stats = (res.stats,) + res.stage_stats
                    # whole-chain aliveness: a stub cluster's clients forward
                    # nothing to the PS, so they must leave the denominator too
                    alive = jnp.asarray(plan.client_alive(), weights.dtype)
                else:
                    res = run_round(agg_cfg, plan, g, state.ef, weights,
                                    global_mask=global_mask,
                                    participate=participate)
                    stage_ef = state.stage_ef
                    all_stats = (res.stats,)
                    alive = jnp.asarray(plan.alive, weights.dtype)

                part = alive if participate is None else participate * alive
                d_total = jnp.maximum(jnp.sum(weights * part), 1e-9)
                flat_new = state.flat_w + res.aggregate / d_total

                new_state = self._pin(SimState(
                    round=state.round + 1, flat_w=flat_new, ef=res.e_new,
                    tcs_prev=tcs_prev, rng=rng, stage_ef=stage_ef))
                # telemetry riders — tiny [K] reductions of state the round
                # already holds, always computed so collection on/off cannot
                # change the jitted program
                ef_mass = banked_mass(res.e_new)
                ef_dead = dead_banked_mass(res.e_new, part)
            with jax.named_scope(scopes.EVAL):
                loss = lr_loss(unflatten_lr(flat_new, pc),
                               fed.x.reshape(-1, pc.input_dim),
                               fed.y.reshape(-1))
            log = RoundLog(
                loss=loss,
                stats=all_stats,
                participation=part,
                ef_mass=ef_mass,
                stage_ef_mass=tuple(banked_mass(e) for e in stage_ef),
                ef_dead_mass=ef_dead,
            )
            return new_state, log

        return one_round

    # -- batched multi-tenant rounds -----------------------------------------

    def init_batched(self, seeds) -> SimState:
        """Stacked :class:`SimState` for B cohorts (leading cohort axis on
        every leaf except the shared round counter)."""
        if self._nested is not None:
            raise ValueError("batched rounds run flat plans; nested "
                             "topologies aggregate per cohort")
        states = [self.init(int(s)) for s in seeds]
        return self._place(SimState(
            round=jnp.int32(0),
            flat_w=jnp.stack([s.flat_w for s in states]),
            ef=jnp.stack([s.ef for s in states]),
            tcs_prev=jnp.stack([s.tcs_prev for s in states]),
            rng=jnp.stack([s.rng for s in states])))

    def round_fn_batched(self) -> Callable:
        """Cohort-batched round closure — B tenants through ONE launch.

        ``(state [B-stacked], plan, participate [B, K] | None) -> (state,
        log)``. The aggregation rides :func:`repro.agg.execute_batched`
        (host) / :func:`repro.agg.device.execute_sharded_batched` (device),
        so B cohorts cost one executor launch — one ``pallas_call`` per
        fused level, one collective wavefront per level on devices — while
        per-cohort EF, §V HopStats, and model trajectories stay exactly
        separated (bitwise equal per cohort to the sequential round on the
        same inputs; see tests/test_batched_rounds.py).
        """
        pc, agg_cfg, k = self.pc, self.agg, self.k
        fed, weights, lr = self.fed, self.weights, self.local_lr
        needs_tcs = agg_cfg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA)
        mesh = self._mesh
        if mesh is None:
            run_batch = execute_batched
        else:
            from repro.agg.device import execute_sharded_batched

            def run_batch(cfg, plan, g, e, w, *, global_mask=None,
                          participate=None):
                return execute_sharded_batched(cfg, plan, g, e, w,
                                               mesh=mesh,
                                               global_mask=global_mask,
                                               participate=participate)

        trace_counter = self.trace_counter

        def one_round(state: SimState, plan: AggPlan,
                      participate: Optional[Array] = None):
            trace_counter.bump()        # runs at trace time only
            with jax.named_scope(scopes.GRADS):
                b = state.flat_w.shape[0]
                keys = jax.vmap(jax.random.split)(state.rng)   # [B, 2, 2]
                rng, kb = keys[:, 0], keys[:, 1]

                def cohort_grads(flat_w, key):
                    params = unflatten_lr(flat_w, pc)
                    bx, by = client_minibatch(fed, key, pc.batch_size)

                    def client_grad(x, y):
                        gr = jax.grad(lr_loss)(params, x, y)
                        return -lr * flatten_lr(gr)

                    return jax.vmap(client_grad)(bx, by)

                g = jax.vmap(cohort_grads)(state.flat_w, kb)   # [B, K, d]

            with jax.named_scope(scopes.AGGREGATE):
                global_mask = None
                tcs_prev = state.tcs_prev
                if needs_tcs:
                    global_mask = jax.vmap(
                        lambda prev, w: tcs_mod.global_mask(
                            tcs_mod.TCSState(prev), w, agg_cfg.q_global))(
                                tcs_prev, state.flat_w)        # [B, d]
                    tcs_prev = state.flat_w

                res = run_batch(agg_cfg, plan, g, state.ef,
                                jnp.broadcast_to(weights, (b, k)),
                                global_mask=global_mask,
                                participate=participate)

                alive = jnp.asarray(plan.alive, weights.dtype)
                alive = jnp.broadcast_to(alive, (b, k))        # [K] | [B, K]
                part = alive if participate is None else participate * alive
                d_total = jnp.maximum(
                    jnp.sum(weights * part, axis=1), 1e-9)     # [B]
                flat_new = state.flat_w + res.aggregate / d_total[:, None]

                new_state = self._pin(SimState(
                    round=state.round + 1, flat_w=flat_new, ef=res.e_new,
                    tcs_prev=tcs_prev, rng=rng))
            with jax.named_scope(scopes.EVAL):
                xs = fed.x.reshape(-1, pc.input_dim)
                ys = fed.y.reshape(-1)
                loss = jax.vmap(lambda w: lr_loss(unflatten_lr(w, pc),
                                                  xs, ys))(flat_new)
            with jax.named_scope(scopes.AGGREGATE):
                ef_mass = banked_mass(res.e_new)           # [B, K]
                ef_dead = jax.vmap(dead_banked_mass)(res.e_new, part)
            log = RoundLog(
                loss=loss,
                stats=(res.stats,),                        # leaves [B, K]
                participation=part,
                ef_mass=ef_mass,
                stage_ef_mass=(),
                ef_dead_mass=ef_dead,
            )
            return new_state, log

        return one_round

    def run_batched(self, rounds: int, *, seeds, eval_every: int = 10,
                    test_x: Optional[Array] = None,
                    test_y: Optional[Array] = None,
                    participate_fn: Optional[Callable] = None,
                    failure_schedule: Optional[FailureSchedule] = None,
                    order_fn: Optional[Callable] = None,
                    topology_schedule: Optional[TopologySchedule] = None,
                    collector=None, flush_every: int = 32):
        """Train B independent cohorts through batched rounds → per-cohort
        curves.

        ``seeds`` (length B) initializes one model/data stream per cohort;
        all cohorts share the constellation (the per-round plan sources
        behave exactly as in :meth:`run`) and every round is ONE batched
        launch. The jitted round specializes once per plan *shape* — the
        cohort count rides the same specialization, audited by
        ``trace_counter`` exactly like the sequential loop. ``collector``
        records one round record per cohort per round, tagged with
        ``cohort=i`` (trace schema 1.1), so telemetry stays queryable per
        tenant. Returns ``{"state", "loss" [rounds][B], "bits" [rounds][B],
        "nnz" [rounds][B], "accuracy" [(round, [B])]}``.
        """
        seeds = list(seeds)
        b = len(seeds)
        state = self.init_batched(seeds)
        topo = self.tree_topology
        if failure_schedule is not None and topo is None:
            raise ValueError("failure_schedule needs tree_topology (chain "
                             "failures go through participate_fn + order_fn)")
        if order_fn is not None and (topo is not None
                                     or topology_schedule is not None):
            raise ValueError("order_fn is a chain-mode knob; trees and "
                             "schedules carry their own topology")
        if topology_schedule is not None and topo is not None:
            raise ValueError("pass either tree_topology or "
                             "topology_schedule, not both")
        if (topology_schedule is not None and len(topology_schedule)
                and isinstance(topology_schedule.plan_at(0), NestedPlan)):
            raise ValueError("batched rounds run flat plans; nested "
                             "topologies aggregate per cohort")

        step = jax.jit(self.round_fn_batched())
        cache = _PlanCache(self.k)

        def plan_for(r: int, state: SimState) -> tuple:
            if topology_schedule is not None:
                raw = topology_schedule.raw_at(r)
                return (topology_schedule.plan_at(r),
                        raw if hasattr(raw, "uplink_bw_bps") else None)
            if topo is not None:
                dead = (tuple(failure_schedule.dead_at(r))
                        if failure_schedule is not None else ())
                key = ("tree", dead)
                plan = cache.get(key, lambda: topo.tree(dead=dead))
                return plan, cache.raw(key)
            if order_fn is not None:
                order = np.asarray(order_fn(r, state), np.int32)
                return cache.get(("order", tuple(order.tolist())),
                                 lambda: order), None
            return cache.get(("chain",), lambda: self.k), None

        if collector is not None:
            collector.configure(
                cfg=self.agg, d=self.d, num_clients=self.k,
                backend=self.backend, cohorts=b,
                topology=("schedule" if topology_schedule is not None
                          else "tree" if topo is not None
                          else "order" if order_fn is not None else "chain"))

        timer = PhaseTimer()
        buf = RoundBuffer()
        pending: list = []
        accs, losses, bits, nnzs = [], [], [], []

        def flush():
            # a span of its own on the trace, not a phase of the next round
            with timer.phase("flush", track="simulator", keep=False) as span:
                logs = _fetch_logs(buf)
            if collector is not None and logs:
                collector.record_span("flush", span.t0_s, span.dur_s,
                                      track="simulator",
                                      args={"rounds": len(logs)})
            for (log, acc), (r, plan, tree, retraces, phases) in zip(
                    logs, pending):
                losses.append(np.asarray(log.loss).tolist())
                st0 = log.stats[0]
                bits.append(np.sum(np.asarray(st0.bits), axis=-1).tolist())
                nnzs.append(np.sum(np.asarray(st0.nnz_out),
                                   axis=-1).tolist())
                if acc is not None:
                    accs.append((r, np.asarray(acc).tolist()))
                if collector is not None:
                    for i in range(b):
                        coh = jax.tree.map(lambda x: np.asarray(x)[i],
                                           log.stats[0])
                        collector.record_round(
                            r, coh, plan=plan, tree=tree,
                            loss=np.asarray(log.loss)[i],
                            participate=np.asarray(log.participation)[i],
                            ef_mass=np.asarray(log.ef_mass)[i],
                            ef_dead_mass=np.asarray(log.ef_dead_mass)[i],
                            retraces=retraces, phases=phases, cohort=i)
            del pending[:]

        for r in range(rounds):
            with timer.phase("plan", track="simulator"):
                plan, tree = plan_for(r, state)
                part = None
                if participate_fn is not None:
                    part = jnp.asarray(participate_fn(r, state))
                    if part.ndim == 1:     # one mask for every cohort
                        part = jnp.broadcast_to(part, (b, self.k))
            with timer.phase("dispatch", track="simulator"):
                state, log = step(state, plan, part)
                acc = None
                if test_x is not None and (r % eval_every == 0
                                           or r == rounds - 1):
                    acc = jax.vmap(
                        lambda w: lr_accuracy(unflatten_lr(w, self.pc),
                                              test_x, test_y))(state.flat_w)
            buf.push((log, acc))
            pending.append((r, plan, tree, self.trace_counter.count,
                            timer.take()))
            if len(buf) >= max(1, flush_every):
                flush()
        flush()
        return {"state": state, "loss": losses, "bits": bits, "nnz": nnzs,
                "accuracy": accs}

    # -- host loop ------------------------------------------------------------
    def run(self, rounds: int, *, seed: int = 0, eval_every: int = 10,
            test_x: Optional[Array] = None, test_y: Optional[Array] = None,
            participate_fn: Optional[Callable] = None,
            failure_schedule: Optional[FailureSchedule] = None,
            order_fn: Optional[Callable] = None,
            topology_schedule: Optional[TopologySchedule] = None,
            scenario=None, collector=None, flush_every: int = 32):
        """→ dict of curves (accuracy, loss, bits/round).

        Per-round topology sources (mutually exclusive):

        * ``failure_schedule`` (needs ``tree_topology``): relay deaths
          re-route the aggregation tree around the dead node and zero its
          participation; its banked EF mass transmits after recovery, as on
          the chain;
        * ``order_fn(r, state) -> [K] int`` permutation: healed/rotated
          chain visiting orders, compiled and cached per distinct order;
        * ``topology_schedule``: a pre-padded
          :class:`~repro.agg.TopologySchedule` — graph-per-round or link
          up/down events, one jit specialization for the whole schedule;
        * ``scenario``: a :class:`repro.scenario.Scenario` (compiled here)
          or a pre-compiled :class:`repro.scenario.CompiledScenario` — its
          schedule and realized participation drive every round, the
          simulator seed is pinned to the spec's ``seed``, and the spec
          dict + realized event stream are embedded in the trace (meta
          ``scenario_spec`` + ``track="scenario"`` spans), so the run is
          bit-reproducible from the spec *or* from its own trace.

        ``collector`` (a :class:`repro.obs.TraceCollector`) records every
        round to a JSONL trace; attaching one never changes the jitted
        round. Round logs stay on device and are materialized with one
        ``device_get`` every ``flush_every`` rounds (plus once at the
        end), so the device backend is not forced to sync per round.
        """
        compiled = None
        if scenario is not None:
            if (participate_fn is not None or failure_schedule is not None
                    or order_fn is not None or topology_schedule is not None
                    or self.tree_topology is not None
                    or self._nested is not None):
                raise ValueError("a scenario carries its own topology and "
                                 "participation — pass it alone")
            from repro.scenario import CompiledScenario, compile_scenario
            compiled = (scenario if isinstance(scenario, CompiledScenario)
                        else compile_scenario(scenario, cfg=self.agg))
            if compiled.num_clients != self.k:
                raise ValueError(f"scenario has {compiled.num_clients} "
                                 f"clients, data has {self.k}")
            # replay determinism: the model/data stream is pinned by the
            # spec, not the call site
            seed = compiled.spec.seed
            topology_schedule = compiled.schedule
        state = self.init(seed)
        topo = self.tree_topology
        if failure_schedule is not None and topo is None:
            raise ValueError("failure_schedule needs tree_topology (chain "
                             "failures go through participate_fn + order_fn)")
        if order_fn is not None and (topo is not None
                                     or topology_schedule is not None
                                     or self._nested is not None):
            raise ValueError("order_fn is a chain-mode knob; trees, nested "
                             "plans and schedules carry their own topology")
        if topology_schedule is not None and (topo is not None
                                              or self._nested is not None):
            raise ValueError("pass either tree_topology/nested_topology or "
                             "topology_schedule, not both")

        if (topology_schedule is not None and len(topology_schedule)
                and isinstance(topology_schedule.plan_at(0), NestedPlan)
                and not state.stage_ef):
            # a schedule of nested plans shares one per-stage unit count
            # (validated by TopologySchedule) → one set of EF tiers
            state = self._place(state._replace(stage_ef=zero_stage_ef(
                topology_schedule.plan_at(0), self.d)))

        step = jax.jit(self.round_fn())
        cache = _PlanCache(self.k)

        def plan_for(r: int, state: SimState) -> tuple:
            """→ (plan, routed AggTree | None — the trace's link model)."""
            if self._nested is not None:
                return self._nested, None
            if topology_schedule is not None:
                raw = topology_schedule.raw_at(r)
                return (topology_schedule.plan_at(r),
                        raw if hasattr(raw, "uplink_bw_bps") else None)
            if topo is not None:
                dead = (tuple(failure_schedule.dead_at(r))
                        if failure_schedule is not None else ())
                key = ("tree", dead)
                plan = cache.get(key, lambda: topo.tree(dead=dead))
                return plan, cache.raw(key)
            if order_fn is not None:
                order = np.asarray(order_fn(r, state), np.int32)
                return cache.get(("order", tuple(order.tolist())),
                                 lambda: order), None
            return cache.get(("chain",), lambda: self.k), None

        if collector is not None:
            extra = {}
            if compiled is not None:
                # the full spec rides in the trace meta: a recorded trace is
                # sufficient to re-run its scenario (scenario_from_trace)
                extra = {"scenario": compiled.spec.name,
                         "scenario_spec": compiled.spec.to_dict()}
            collector.configure(
                cfg=self.agg, d=self.d, num_clients=self.k,
                backend=self.backend,
                topology=("scenario" if compiled is not None
                          else "nested" if self._nested is not None
                          else "schedule" if topology_schedule is not None
                          else "tree" if topo is not None
                          else "order" if order_fn is not None else "chain"),
                **extra)
            if compiled is not None:
                # realized event stream → span records on the scenario
                # track (t0_s/dur_s are in *rounds*, not seconds)
                for ev in compiled.events:
                    collector.record_span(
                        ev["name"], float(ev["round"]), float(ev["rounds"]),
                        track="scenario",
                        args={"kind": ev["kind"], **(ev.get("args") or {})})

        timer = PhaseTimer()
        buf = RoundBuffer()
        pending: list = []      # (round, plan, tree, retraces, phases)
        accs, losses, bits, nnzs = [], [], [], []

        def flush():
            # a span of its own on the trace, not a phase of the next round
            with timer.phase("flush", track="simulator", keep=False) as span:
                logs = _fetch_logs(buf)
            if collector is not None and logs:
                collector.record_span("flush", span.t0_s, span.dur_s,
                                      track="simulator",
                                      args={"rounds": len(logs)})
            for (log, acc), (r, plan, tree, retraces, phases) in zip(
                    logs, pending):
                losses.append(float(log.loss))
                bits.append(float(sum(np.sum(np.asarray(s.bits))
                                      for s in log.stats)))
                nnzs.append(float(sum(np.sum(np.asarray(s.nnz_out))
                                      for s in log.stats)))
                if acc is not None:
                    accs.append((r, float(acc)))
                if collector is not None:
                    collector.record_round(
                        r, log.stats, plan=plan, tree=tree, loss=log.loss,
                        participate=log.participation, ef_mass=log.ef_mass,
                        stage_ef_mass=log.stage_ef_mass,
                        ef_dead_mass=log.ef_dead_mass, retraces=retraces,
                        phases=phases)
            del pending[:]

        for r in range(rounds):
            with timer.phase("plan", track="simulator"):
                plan, tree = plan_for(r, state)
                part = None
                if compiled is not None:
                    part = jnp.asarray(compiled.participate_at(r))
                elif participate_fn is not None:
                    part = participate_fn(r, state)
            # stranded/dead clients are masked inside execute via plan.alive
            with timer.phase("dispatch", track="simulator"):
                state, log = step(state, plan, part)
                acc = None
                if test_x is not None and (r % eval_every == 0
                                           or r == rounds - 1):
                    acc = lr_accuracy(unflatten_lr(state.flat_w, self.pc),
                                      test_x, test_y)
            # logs stay un-fetched on device until the next flush
            buf.push((log, acc))
            pending.append((r, plan, tree, self.trace_counter.count,
                            timer.take()))
            if len(buf) >= max(1, flush_every):
                flush()
        flush()
        return {"state": state, "loss": losses, "bits": bits, "nnz": nnzs,
                "accuracy": accs}
