"""The assembled distributed train step (and serve steps).

One jit, three phases (DESIGN §5):

  1. per-client grads — shard_map manual over the DP axes (pod, data), the
     model axis stays *auto* so GSPMD runs TP inside; the loss is averaged
     over the local shard only, so gradients come out per-client
     (stacked [K_dp, …]), NOT psum'd;
  2. sparse incremental aggregation — the rotated ring (core/ring.py) over
     the combined (pod, data) ring — the paper's K-client multi-hop chain,
     one chain per segment — operating in the *shard-aligned flat space*
     (core/flat_layout.py): gradients are flattened locally inside the
     manual shard_map, so no resharding collectives ever touch the
     gradient-sized buffers (EXPERIMENTS §Perf it.4);
  3. ZeRO optimizer — flat fp32 master, fully sharded, elementwise update;
     the downlink shard_map rebuilds the param pytree (dp all-gather per
     model column = the paper's w^{t+1} broadcast, counted separately from
     the uplink cost model).

``build_serve_step``/``build_prefill_step`` produce the inference
entrypoints the decode/prefill dry-run cells lower.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
from repro.core import ring as ring_mod
from repro.core import sparsify as sp
from repro.core.algorithms import AggConfig
from repro.core.flat_layout import FlatLayout
from repro.models import model as model_mod
from repro.models import partition
from repro.obs import scopes
from repro.optim import optimizers as opt_mod
from repro.optim.schedule import lr_schedule
from repro.train.state import TrainConfig, TrainState

Array = jax.Array


def dp_axes(mesh) -> tuple:
    return partition.batch_axes(mesh)


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def flat_spec(mesh) -> P:
    """Sharding of the flat master/opt/aggregate: model-major, then ring."""
    return P(("model",) + dp_axes(mesh))


# ---------------------------------------------------------------------------
# Nested (staged) aggregation topology plumbing
# ---------------------------------------------------------------------------

def nested_stage_axes(mesh, n_stages: int) -> tuple:
    """Per-stage mesh axes for a nested plan over this mesh's DP ring.

    Stage 0 runs on the *minor* DP axis (client k = pod·K_d + data ⇒
    mesh-aligned clusters), each later stage one axis up; the last stage
    takes whatever DP axes remain as one flattened ring. For the
    (pod, data) production mesh and a 2-stage plan this is
    ``("data", "pod")`` — exactly ``core/hierarchical.py``'s mapping.
    """
    dp = dp_axes(mesh)
    if len(dp) < n_stages:
        raise ValueError(f"a {n_stages}-stage nested plan needs ≥"
                         f"{n_stages} DP axes; mesh has {dp}")
    axes = [dp[len(dp) - 1 - s] for s in range(n_stages - 1)]
    rest = dp[:len(dp) - (n_stages - 1)]
    axes.append(rest[0] if len(rest) == 1 else tuple(rest))
    return tuple(axes)


def _stage_order(axes) -> tuple:
    """Flatten per-stage axes into one name tuple, stage order."""
    out: list = []
    for a in axes:
        out.extend(a if isinstance(a, tuple) else (a,))
    return tuple(out)


def nested_flat_spec(mesh, axes) -> P:
    """Flat master/opt/aggregate sharding under staged aggregation: rank
    coords own [stage-0 segment, stage-1 sub-segment, …] — the dp axes in
    *stage* order (reversed), the hierarchical P(("model","data","pod"))
    layout generalized."""
    return P(("model",) + _stage_order(axes))


def _resolve_topology(mesh, topology):
    """→ (flat topology | None, NestedPlan | None, stage axes | None)."""
    from repro.agg.nested import NestedPlan, compile_nested, pod_ring_nested

    nested = None
    if isinstance(topology, str) and topology == "hierarchical":
        dp = dp_axes(mesh)
        if len(dp) < 2:
            raise ValueError(f"'hierarchical' needs ≥2 DP axes (pod, "
                             f"data); mesh has {dp}")
        k_minor = mesh.shape[dp[-1]]
        nested = pod_ring_nested(dp_size(mesh) // k_minor, k_minor)
    elif isinstance(topology, NestedPlan):
        nested = topology
    elif hasattr(topology, "nested_stages"):
        nested = compile_nested(topology, num_clients=dp_size(mesh))
    if nested is None:
        return topology, None, None
    if nested.num_clients != dp_size(mesh):
        raise ValueError(f"nested topology has {nested.num_clients} "
                         f"clients but the mesh provides "
                         f"{dp_size(mesh)} DP ranks")
    return None, nested, nested_stage_axes(mesh, nested.num_stages)


def _stage_ef_dims(mesh, axes, d_flat: int) -> tuple:
    """Flat length of each upper EF tier: stage s's tier covers one
    stage-(s−1) output segment per rank column."""
    dims = []
    prefix = 1
    for a in axes[:-1]:
        names = a if isinstance(a, tuple) else (a,)
        for n in names:
            prefix *= mesh.shape[n]
        dims.append(d_flat // prefix)
    return tuple(dims)


@functools.lru_cache(maxsize=None)
def _layout_cached(cfg: ModelConfig, mesh) -> FlatLayout:
    template = model_mod.param_specs(cfg)
    return FlatLayout(template, partition.param_pspecs(cfg, mesh), mesh)


def make_layout(cfg: ModelConfig, mesh) -> FlatLayout:
    try:
        return _layout_cached(cfg, mesh)
    except TypeError:                      # unhashable mesh fallback
        template = model_mod.param_specs(cfg)
        return FlatLayout(template, partition.param_pspecs(cfg, mesh), mesh)


def global_q(tc: TrainConfig, d_flat: int) -> int:
    return max(1, int(tc.q_frac * d_flat))


def _segment_agg_cfg(tc: TrainConfig, mesh, d_flat: int) -> AggConfig:
    """Per-segment AggConfig: the global budget split over all segments."""
    n_segments = dp_size(mesh) * model_size(mesh)
    q = global_q(tc, d_flat)
    q_seg = ring_mod.segment_budget(q, n_segments)
    kw = dict(q=q_seg)
    if tc.needs_tcs():
        if q_seg == 0:
            # global budget smaller than the segment count: nothing to
            # split — the sub-budgets must not re-inflate §V bits
            kw.update(q_local=0, q_global=0)
        else:
            ql = max(1, round(q_seg * tc.agg.q_local / max(tc.agg.q, 1))
                     ) if tc.agg.q_local else max(1, q_seg // 10)
            kw.update(q_local=ql, q_global=max(q_seg - ql, 1))
    return dataclasses.replace(tc.agg, **kw)


def _ef_spec(tc: TrainConfig, mesh) -> P:
    """Sharding of the per-client EF: a [K_dp, D_pad] array, or with
    ``tc.ef_flat`` its flat [K_dp·D_pad] form, rank (client, column)
    owning one contiguous block."""
    dp = dp_axes(mesh)
    return P(dp + ("model",)) if tc.ef_flat else P(dp, "model")


def _check_ef_flat(tc: TrainConfig, nested, cohorts: int) -> None:
    if tc.ef_flat and (nested is not None or cohorts > 1):
        raise ValueError("ef_flat runs flat, single-tenant topologies")


def _model_axis_index(mesh):
    if "model" in mesh.axis_names:
        return jax.lax.axis_index("model")
    return jnp.int32(0)


# ---------------------------------------------------------------------------
# State init
# ---------------------------------------------------------------------------

def _master_from_params(cfg: ModelConfig, mesh, layout: FlatLayout, params,
                        order=None):
    """Flat fp32 master from the param pytree (shard-aligned, in-shard_map).

    ``order`` overrides the rank→slice mapping (a flattened axis-name
    tuple): nested topologies own the flat space in stage order (reversed
    dp), see :func:`nested_flat_spec`.
    """
    dp = dp_axes(mesh)
    k_dp = dp_size(mesh)
    seg = layout.n_local // k_dp
    manual = set(mesh.axis_names)
    idx_axes = dp if order is None else order
    out_spec = (flat_spec(mesh) if order is None
                else P(("model",) + tuple(order)))

    def fn(p):
        m_idx = _model_axis_index(mesh)
        col = layout.local_flatten(jax.tree.leaves(p), m_idx, jnp.float32)
        if k_dp > 1:
            r = jax.lax.axis_index(idx_axes)
            return jax.lax.dynamic_slice(col, (r * seg,), (seg,))
        return col

    return compat.shard_map(
        fn, mesh=mesh, in_specs=(layout.param_in_specs(),),
        out_specs=out_spec, axis_names=manual,
    )(params)


def init_state(cfg: ModelConfig, tc: TrainConfig, mesh, rng,
               topology: Any = None, cohorts: int = 1) -> TrainState:
    """Materializing init (small models / tests). Dry-run uses eval_shape.

    ``topology`` must match the one later given to
    :func:`build_train_step`: a nested topology adds the upper EF tiers
    (``stage_ef``) and lays the flat master out in stage order.
    ``cohorts=B`` stacks B independently-initialized tenant states (one
    rng split each) with a leading cohort axis on every leaf — the state
    :func:`build_train_step` with the same ``cohorts`` consumes.
    """
    if cohorts > 1:
        if _resolve_topology(mesh, topology)[1] is not None:
            raise ValueError("cohort batches run flat topologies; nested "
                             "plans train per tenant")
        states = [init_state(cfg, tc, mesh, k, topology)
                  for k in jax.random.split(rng, cohorts)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    layout = make_layout(cfg, mesh)
    k_dp = dp_size(mesh)
    _, nested, n_axes = _resolve_topology(mesh, topology)
    _check_ef_flat(tc, nested, cohorts)
    params = model_mod.init_params(cfg, rng)
    order = None if nested is None else _stage_order(n_axes)
    master = _master_from_params(cfg, mesh, layout, params, order=order)
    opt = opt_mod.init_flat(tc.opt, layout.d_flat, like=master)
    ef = jnp.zeros((k_dp * layout.d_flat,) if tc.ef_flat
                   else (k_dp, layout.d_flat), jnp.dtype(tc.ef_dtype))
    stage_ef = None
    if nested is not None:
        stage_ef = tuple(
            jnp.zeros((k_dp, dim), jnp.dtype(tc.ef_dtype))
            for dim in _stage_ef_dims(mesh, n_axes, layout.d_flat))
    tcs_prev = None
    if tc.needs_tcs():
        tcs_prev = jax.tree.map(lambda p: p.astype(jnp.dtype(tc.agg_dtype)),
                                params)
    return TrainState(step=jnp.int32(0), params=params, master=master,
                      opt=opt, ef=ef, tcs_prev=tcs_prev, stage_ef=stage_ef)


def _cohort_spec(spec: P) -> P:
    """Prepend an unsharded leading cohort axis to a PartitionSpec."""
    return P(*((None,) + tuple(spec)))


def state_shardings(cfg: ModelConfig, tc: TrainConfig, mesh,
                    topology: Any = None, cohorts: int = 1):
    """NamedSharding pytree matching TrainState (pass the same
    ``topology``/``cohorts`` as :func:`build_train_step` — cohort batches
    keep every per-tenant leaf replicated along the leading cohort
    axis)."""
    _, nested, n_axes = _resolve_topology(mesh, topology)
    fs = flat_spec(mesh) if nested is None else nested_flat_spec(mesh,
                                                                 n_axes)
    dp = dp_axes(mesh)
    coh = _cohort_spec if cohorts > 1 else (lambda s: s)
    ns = lambda s: NamedSharding(mesh, coh(s))
    p_specs = jax.tree.map(ns, partition.param_pspecs(cfg, mesh),
                           is_leaf=lambda x: isinstance(x, P))
    opt_m = None if tc.opt.name == "sgd" else ns(fs)
    opt_v = ns(fs) if tc.opt.name == "adamw" else None
    tcs = (jax.tree.map(ns, partition.param_pspecs(cfg, mesh),
                        is_leaf=lambda x: isinstance(x, P))
           if tc.needs_tcs() else None)
    stage_ef = None
    if nested is not None:
        stage_ef = tuple(ns(P(dp, "model"))
                         for _ in range(nested.num_stages - 1))
    return TrainState(
        step=ns(P()),
        params=p_specs,
        master=ns(fs),
        opt=opt_mod.FlatOptState(step=ns(P()), m=opt_m, v=opt_v),
        ef=ns(_ef_spec(tc, mesh)),
        tcs_prev=tcs,
        stage_ef=stage_ef,
    )


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, tc: TrainConfig, mesh,
                     topology: Any = None, telemetry: bool = False,
                     cohorts: int = 1):
    """Returns train_step(state, batch) → (state, metrics). jit-ready.

    ``cohorts=B`` builds the multi-tenant batched step: ``state`` carries
    a leading cohort axis on every leaf (:func:`init_state` with the same
    ``cohorts``), ``batch`` leaves carry ``[B, global_batch, …]``, and the
    B tenants share one compiled step — phase 1 vmaps the per-client
    grads, phase 2 rides
    :func:`repro.agg.device.run_plan_segments_batched` (one ppermute
    wavefront per level for all cohorts), phase 3 vmaps the flat
    optimizer. Metrics leaves come back per cohort (``[B]``). Flat
    topologies only; per cohort the math is the sequential step's.

    ``telemetry=True`` adds the fault-exposure metrics the trace
    subsystem records (``ef_mass`` = Σ_k ‖e_k‖₁ over every EF tier,
    ``ef_dead_mass`` = :func:`repro.runtime.fault.dead_banked_mass` over
    the round's non-participants); off by default so the historic metrics
    pytree — and the compiled step — are unchanged.

    ``topology`` selects the aggregation route over the K_dp clients:
    ``None`` keeps the rotated ring (the paper chain, bit-exact to the
    historic path), everything else — an :class:`repro.agg.AggPlan`, an
    ``AggTree``, a chain order, or a ``ConstellationGraph`` — is compiled
    via :func:`repro.agg.compile_plan` and lowered onto the same shard_map
    ring by :func:`repro.agg.device.run_plan_segments_local`, so routed
    constellation trees run multi-device with the ring's wire format and
    §V accounting.

    Nested (staged) topologies — ``"hierarchical"``, a
    :class:`~repro.agg.nested.NestedPlan`, or a routed
    :class:`~repro.topo.routing.NestedTopology` — lower through
    :func:`repro.agg.device.run_nested_segments_local` instead: stage 0
    aggregates on the minor DP axis (pod-internal ICI), later stages
    relay per-cluster partials up the remaining axes (pod-seam DCI), the
    upper EF tiers persist in ``state.stage_ef``, and the flat
    master/optimizer own the stage-order layout
    (:func:`nested_flat_spec`) — pass the same ``topology`` to
    :func:`init_state`/:func:`state_shardings`. Metrics gain
    ``agg_bits_relay``, the last stage's (scarce-link) §V bits.
    """
    from repro.agg.device import (ring_chain_plan,
                                  run_nested_segments_local,
                                  run_plan_segments_batched,
                                  run_plan_segments_local)
    from repro.agg.plan import AggPlan, compile_plan

    layout = make_layout(cfg, mesh)
    dp = dp_axes(mesh)
    k_dp = dp_size(mesh)
    seg = layout.n_local // k_dp
    agg_cfg = _segment_agg_cfg(tc, mesh, layout.d_flat)
    _, nested_plan, n_axes = _resolve_topology(mesh, topology)
    _check_ef_flat(tc, nested_plan, cohorts)
    ef_sp = _ef_spec(tc, mesh)
    if cohorts > 1 and nested_plan is not None:
        raise ValueError("cohort batches run flat topologies; nested "
                         "plans train per tenant")
    if nested_plan is not None:
        agg_plan = nested_plan
        fs = nested_flat_spec(mesh, n_axes)
        gather_axes = _stage_order(n_axes)
    else:
        if topology is None:
            agg_plan = ring_chain_plan(k_dp)
        elif isinstance(topology, AggPlan):
            agg_plan = topology
        else:
            agg_plan = compile_plan(topology, num_clients=k_dp)
        if agg_plan.num_clients != k_dp:
            raise ValueError(f"topology has {agg_plan.num_clients} clients "
                             f"but the mesh provides {k_dp} DP ranks")
        fs = flat_spec(mesh)
        gather_axes = dp
    agg_dt = jnp.dtype(tc.agg_dtype)
    manual_axes = set(mesh.axis_names)
    needs_tcs = tc.needs_tcs()
    qg_total = 0
    if needs_tcs:
        qg_total = max(1, int(
            global_q(tc, layout.d_flat) * agg_cfg.q_global
            / max(agg_cfg.q_global + agg_cfg.q_local, 1)))

    # SSM/hybrid params are model-replicated (mixed-group in_proj; DESIGN
    # §5) — without help the TP axis recomputes every mamba block M×
    # (measured: 16× FLOPs on mamba2-130m, EXPERIMENTS §Perf it.3). Shard
    # the local batch over `model` instead: the TP axis becomes a second
    # DP axis for compute; the ring in_specs then insert one model-axis
    # all-reduce per grad leaf (2·|grads| wire ≪ 16× compute).
    # tc.fsdp_compute extends the same layout to dense archs (weights stay
    # model-sharded → GSPMD gathers them per layer, FSDP-style).
    batch_over_model = cfg.family in ("ssm", "hybrid") or tc.fsdp_compute

    # ---- phase 1: per-client gradients ------------------------------------
    def per_client(params, batch):
        if batch_over_model and "model" in mesh.axis_names:
            m = mesh.shape["model"]
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, P("model", *([None] * (x.ndim - 1))))
                if x.shape[0] % m == 0 else x, batch)

        def local_loss(p):
            return model_mod.loss_fn(cfg, p, batch)
        (loss, metrics), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        loss = jax.lax.pmean(loss, dp)
        # the model's counters (empty for most families), over all clients
        counters = jax.lax.psum(metrics["counters"], dp)
        grads = jax.tree.map(lambda g: g[None], grads)   # stack client axis
        return grads, loss, counters

    # ---- phase 2: sparse incremental aggregation (flat, local layout) -----
    def _col_and_mask(grads_tree, params_tree, prev_tree):
        m_idx = _model_axis_index(mesh)
        g_leaves = [l[0] for l in jax.tree.leaves(grads_tree)]
        col = layout.local_flatten(g_leaves, m_idx, agg_dt)

        mask_col = None
        if needs_tcs:
            p_col = layout.local_flatten(jax.tree.leaves(params_tree),
                                         m_idx, jnp.float32)
            q_col = layout.local_flatten(jax.tree.leaves(prev_tree),
                                         m_idx, jnp.float32)
            delta = p_col - q_col
            # identical global threshold on every column: counts psum over
            # `model` only (columns partition coordinates; dp replicates).
            # tau_impl="hist" collapses the search to ONE psum'd histogram
            # (D2, F) instead of hist_rounds sequential count+psum rounds —
            # fewer collective round-trips on the device path, same τ bits.
            axis = "model" if "model" in mesh.axis_names else None
            tau_g = sp.threshold_for_topq(
                delta, qg_total, branch=agg_cfg.hist_branch,
                rounds=agg_cfg.hist_rounds, axis_name=axis,
                tau_impl=agg_cfg.tau_impl)
            mask_col = jnp.where(jnp.any(delta != 0),
                                 (jnp.abs(delta) >= tau_g).astype(agg_dt),
                                 jnp.zeros_like(delta, agg_dt))
        return col, mask_col

    def ring_fn(grads_tree, ef_l, w_l, part_l, params_tree, prev_tree):
        col, mask_col = _col_and_mask(grads_tree, params_tree, prev_tree)
        final, ef_new, stats = run_plan_segments_local(
            agg_cfg, agg_plan, col, ef_l if tc.ef_flat else ef_l[0],
            w_l[0], axis=dp, global_mask_local=mask_col,
            participate=part_l[0], transport="static")
        stats = jax.tree.map(
            lambda s: jax.lax.psum(s, tuple(manual_axes)), stats)
        return final, ef_new if tc.ef_flat else ef_new[None], stats

    def nested_ring_fn(grads_tree, ef_l, se_l, w_l, part_l, params_tree,
                       prev_tree):
        col, mask_col = _col_and_mask(grads_tree, params_tree, prev_tree)
        final, ef_new, se_new, sts = run_nested_segments_local(
            agg_cfg, agg_plan, col, ef_l[0],
            tuple(x[0] for x in se_l), w_l[0], axes=n_axes,
            global_mask_local=mask_col, participate=part_l[0])
        total = ring_mod.RingStats(
            bits=sum(s.bits for s in sts),
            nnz=sum(s.nnz for s in sts),
            err_sq=sum(s.err_sq for s in sts))
        total, relay_bits = jax.tree.map(
            lambda s: jax.lax.psum(s, tuple(manual_axes)),
            (total, sts[-1].bits))
        return (final, ef_new[None], tuple(x[None] for x in se_new),
                total, relay_bits)

    # ---- phase 3b: downlink (flat master → param pytree) -------------------
    def downlink_fn(master_l):
        m_idx = _model_axis_index(mesh)
        # nested topologies own the flat space in stage order — gather in
        # that order so the column reassembles coordinate-contiguously
        col = (jax.lax.all_gather(master_l, gather_axes, axis=0, tiled=True)
               if k_dp > 1 else master_l)
        leaves = layout.local_unflatten(col, m_idx)
        return layout.treedef.unflatten(leaves)

    empty_param_specs = jax.tree.map(lambda _: P(), model_mod.param_specs(cfg))

    def train_step(state: TrainState, batch: dict):
        batch = dict(batch)
        weights = batch.pop("weights", None)
        participate = batch.pop("participate", None)
        if weights is None:
            weights = jnp.full((k_dp,), 1.0 / k_dp, jnp.float32)
        if participate is None:
            participate = jnp.ones((k_dp,), jnp.float32)

        # phase 1 — per-client grads (model axis auto inside)
        with jax.named_scope(scopes.GRADS):
            grads_stacked, loss, counters = compat.shard_map(
                per_client,
                mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), state.params),
                          jax.tree.map(
                              lambda l: P(dp, *([None] * (l.ndim - 1))),
                              batch)),
                out_specs=(jax.tree.map(
                    lambda l: P(dp, *([None] * l.ndim)), state.params), P(),
                    P()),
                axis_names=set(dp),
            )(state.params, batch)

        # phase 2 — ring aggregation (manual over every axis; the in_specs
        # reshard grads to their param-aligned shardings, which is also the
        # model-axis grad all-reduce for model-replicated leaves)
        with jax.named_scope(scopes.AGGREGATE):
            params_in = state.params
            prev_in = state.tcs_prev if needs_tcs else state.params
            stats_specs = jax.tree.map(lambda _: P(),
                                       ring_mod.RingStats(0., 0., 0.))
            stage_ef_new = state.stage_ef
            relay_bits = None
            if nested_plan is None:
                agg_flat, ef_new, stats = compat.shard_map(
                    ring_fn,
                    mesh=mesh,
                    in_specs=(layout.grads_in_specs(dp), ef_sp, P(dp),
                              P(dp), layout.param_in_specs(),
                              layout.param_in_specs()),
                    out_specs=(fs, ef_sp, stats_specs),
                    axis_names=manual_axes,
                )(grads_stacked, state.ef, weights, participate, params_in,
                  prev_in)
            else:
                se_specs = tuple(P(dp, "model") for _ in state.stage_ef)
                agg_flat, ef_new, stage_ef_new, stats, relay_bits = \
                    compat.shard_map(
                        nested_ring_fn,
                        mesh=mesh,
                        in_specs=(layout.grads_in_specs(dp), P(dp, "model"),
                                  se_specs, P(dp), P(dp),
                                  layout.param_in_specs(),
                                  layout.param_in_specs()),
                        out_specs=(fs, P(dp, "model"), se_specs, stats_specs,
                                   P()),
                        axis_names=manual_axes,
                    )(grads_stacked, state.ef, state.stage_ef, weights,
                      participate, params_in, prev_in)

        # phase 3 — ZeRO flat optimizer
        with jax.named_scope(scopes.OPTIMIZER):
            total_w = jnp.maximum(jnp.sum(weights * participate), 1e-9)
            grad_est = agg_flat.astype(jnp.float32) / total_w
            lr_scale = lr_schedule(state.step, warmup=tc.lr_warmup,
                                   decay_steps=tc.lr_decay_steps)
            master_new, opt_new = opt_mod.apply_flat(
                tc.opt, state.opt, state.master, grad_est, lr_scale)
            master_new = jax.lax.with_sharding_constraint(
                master_new, NamedSharding(mesh, fs))

            # downlink — w^{t+1} broadcast
            params_new = compat.shard_map(
                downlink_fn, mesh=mesh, in_specs=(fs,),
                out_specs=layout.param_out_specs(), axis_names=manual_axes,
            )(master_new)

            tcs_prev_new = state.tcs_prev
            if needs_tcs:
                tcs_prev_new = jax.tree.map(
                    lambda p: p.astype(jnp.dtype(tc.agg_dtype)), state.params)
            step_new = state.step + 1

        metrics = {
            "loss": loss,
            "agg_bits": stats.bits,
            "agg_nnz": stats.nnz,
            "agg_err_sq": stats.err_sq,
            "lr_scale": lr_scale,
            **counters,
        }
        if relay_bits is not None:
            # the scarce-link tier (pod-seam DCI / inter-cluster relay)
            metrics["agg_bits_relay"] = relay_bits
        with jax.named_scope(scopes.AGGREGATE):
            if telemetry:
                from repro.runtime.fault import dead_banked_mass
                metrics["ef_mass"] = (
                    jnp.sum(jnp.abs(ef_new))
                    + sum(jnp.sum(jnp.abs(se)) for se in stage_ef_new or ()))
                metrics["ef_dead_mass"] = dead_banked_mass(
                    ef_new.reshape(k_dp, -1), participate)
        new_state = TrainState(step=step_new, params=params_new,
                               master=master_new, opt=opt_new, ef=ef_new,
                               tcs_prev=tcs_prev_new, stage_ef=stage_ef_new)
        return new_state, metrics

    if cohorts == 1:
        return train_step

    # ---- cohort-batched step (B tenants, one compiled program) -------------
    b_coh = cohorts

    def _coh_specs(tree):
        return jax.tree.map(_cohort_spec, tree,
                            is_leaf=lambda x: isinstance(x, P))

    fs_b = _cohort_spec(fs)

    def ring_fn_b(grads_tree, ef_l, w_l, part_l, params_tree, prev_tree):
        col, mask_col = jax.vmap(_col_and_mask)(grads_tree, params_tree,
                                                prev_tree)
        final, ef_new, stats = run_plan_segments_batched(
            agg_cfg, agg_plan, col, ef_l[:, 0], w_l[:, 0], axis=dp,
            global_mask_local=mask_col, participate=part_l[:, 0],
            transport="static")
        stats = jax.tree.map(
            lambda s: jax.lax.psum(s, tuple(manual_axes)), stats)
        return final, ef_new[:, None], stats

    def downlink_fn_b(master_l):
        m_idx = _model_axis_index(mesh)
        col = (jax.lax.all_gather(master_l, gather_axes, axis=1, tiled=True)
               if k_dp > 1 else master_l)
        return jax.vmap(lambda c: layout.treedef.unflatten(
            layout.local_unflatten(c, m_idx)))(col)

    def train_step_cohorts(state: TrainState, batch: dict):
        batch = dict(batch)
        weights = batch.pop("weights", None)
        participate = batch.pop("participate", None)
        if weights is None:
            weights = jnp.full((k_dp,), 1.0 / k_dp, jnp.float32)
        if participate is None:
            participate = jnp.ones((k_dp,), jnp.float32)
        weights = jnp.broadcast_to(weights, (b_coh, k_dp))
        participate = jnp.broadcast_to(participate, (b_coh, k_dp))

        # phase 1 — per-client grads, one partial-manual shard_map per
        # cohort (the model axis stays auto inside, which XLA only supports
        # without a vmapped batch dim; grads are embarrassingly parallel so
        # looping loses nothing — phase 2 is where cohorts share the wire)
        with jax.named_scope(scopes.GRADS):
            g_list, l_list, c_list = [], [], []
            for i in range(b_coh):
                params_i = jax.tree.map(lambda p: p[i], state.params)
                batch_i = jax.tree.map(lambda x: x[i], batch)
                g_i, l_i, c_i = compat.shard_map(
                    per_client,
                    mesh=mesh,
                    in_specs=(jax.tree.map(lambda _: P(), params_i),
                              jax.tree.map(
                                  lambda l: P(dp, *([None] * (l.ndim - 1))),
                                  batch_i)),
                    out_specs=(jax.tree.map(
                        lambda l: P(dp, *([None] * l.ndim)), params_i), P(),
                        P()),
                    axis_names=set(dp),
                )(params_i, batch_i)
                g_list.append(g_i)
                l_list.append(l_i)
                c_list.append(c_i)
            grads_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *g_list)
            loss = jnp.stack(l_list)
            counters = jax.tree.map(lambda *xs: jnp.stack(xs), *c_list)

        # phase 2 — batched ring aggregation: B cohorts, one wavefront
        with jax.named_scope(scopes.AGGREGATE):
            params_in = state.params
            prev_in = state.tcs_prev if needs_tcs else state.params
            stats_specs = jax.tree.map(lambda _: P(),
                                       ring_mod.RingStats(0., 0., 0.))
            agg_flat, ef_new, stats = compat.shard_map(
                ring_fn_b,
                mesh=mesh,
                in_specs=(_coh_specs(layout.grads_in_specs(dp)),
                          P(None, dp, "model"), P(None, dp), P(None, dp),
                          _coh_specs(layout.param_in_specs()),
                          _coh_specs(layout.param_in_specs())),
                out_specs=(fs_b, P(None, dp, "model"), stats_specs),
                axis_names=manual_axes,
            )(grads_stacked, state.ef, weights, participate, params_in,
              prev_in)

        # phase 3 — ZeRO flat optimizer, vmapped per cohort
        with jax.named_scope(scopes.OPTIMIZER):
            total_w = jnp.maximum(jnp.sum(weights * participate, axis=-1),
                                  1e-9)
            grad_est = agg_flat.astype(jnp.float32) / total_w[:, None]
            lr_scale = lr_schedule(state.step, warmup=tc.lr_warmup,
                                   decay_steps=tc.lr_decay_steps)
            master_new, opt_new = jax.vmap(
                lambda o, ms, gr, ls: opt_mod.apply_flat(tc.opt, o, ms, gr,
                                                         ls))(
                state.opt, state.master, grad_est, lr_scale)
            master_new = jax.lax.with_sharding_constraint(
                master_new, NamedSharding(mesh, fs_b))

            params_new = compat.shard_map(
                downlink_fn_b, mesh=mesh, in_specs=(fs_b,),
                out_specs=_coh_specs(layout.param_out_specs()),
                axis_names=manual_axes,
            )(master_new)

            tcs_prev_new = state.tcs_prev
            if needs_tcs:
                tcs_prev_new = jax.tree.map(
                    lambda p: p.astype(jnp.dtype(tc.agg_dtype)), state.params)
            step_new = state.step + 1

        metrics = {
            "loss": loss,
            "agg_bits": stats.bits,
            "agg_nnz": stats.nnz,
            "agg_err_sq": stats.err_sq,
            "lr_scale": lr_scale,
            **counters,
        }
        with jax.named_scope(scopes.AGGREGATE):
            if telemetry:
                from repro.runtime.fault import dead_banked_mass
                metrics["ef_mass"] = jnp.sum(jnp.abs(ef_new), axis=(1, 2))
                metrics["ef_dead_mass"] = jax.vmap(dead_banked_mass)(
                    ef_new.reshape(b_coh, k_dp, -1), participate)
        new_state = TrainState(step=step_new, params=params_new,
                               master=master_new, opt=opt_new, ef=ef_new,
                               tcs_prev=tcs_prev_new,
                               stage_ef=state.stage_ef)
        return new_state, metrics

    return train_step_cohorts


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def build_serve_step(cfg: ModelConfig, mesh):
    """decode: (params, cache, token [B], pos) → (next_token [B], cache)."""

    def serve_step(params, cache, token, pos):
        logits, cache = model_mod.decode_step(cfg, params, cache, token, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return serve_step


def build_prefill_step(cfg: ModelConfig, mesh):
    def prefill_step(params, cache, tokens, extra=None):
        kw = {}
        if extra is not None:
            kw = {k: v for k, v in extra.items()}
        logits, cache = model_mod.prefill(cfg, params, tokens, cache, **kw)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return prefill_step
