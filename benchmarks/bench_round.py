"""Whole-round aggregation benchmark → ``BENCH_agg_round.json``.

Measures one full aggregation round — every node step of the padded
``(L, W)`` schedule — for all five algorithms over a chain plan and a
routed-tree plan, on the host executor (``repro.agg.execute``) and on the
8-device shard_map lowering (``repro.agg.execute_sharded``), with both the
exact ``lax.top_k`` sparsifier and the streaming threshold sparsifier.
Wall-times are what this machine can honestly measure; the metric that
transfers to TPU is structural — **HBM sweeps per node step** — where the
fused Pallas path is strictly smaller for every algorithm (the aggregation
round is memory-bound, so sweep count bounds achievable wall-time).

Sweep counting rule (one "sweep" = one streaming pass over a d-length
vector, however many operand streams ride along — a fused kernel reading
(g, e, γ_in) and writing (γ_out, e') in one grid walk is ONE sweep):

    stage            unfused  fused  note
    g̃/γ̃ materialize       1      1  sparsifier state needs it jnp-side
    sparsifier (τ/mask)    3      3  top_k sort ≈3 sweeps; threshold =
                                     hist_rounds count sweeps (kernel)
    select + EF            2      1  γ̄=keep(g̃) and e'=g̃−γ̄ fuse into the
                                     sparsify_ef / cl_fuse kernel
    IA combine          1 (0)  1 (0)  γ_out=γ_in+γ̄; 0 for the CL family
                                     (already inside γ̃ / cl_fuse)
    §V support counts   1 (2)      0  nnz (+ off-mask nnz for TC) fuse
                                     into the kernels' accumulators

The fused table assumes the TC global mask *streams* — since the
lane-shared block spec in ``kernels/level.py`` the 1-D mask rides into the
kernels once per block with no ``[W, d]`` HBM broadcast, so the counted
sweeps are what actually executes (the broadcast was an uncounted extra
write + W-fold read before).

Run ``PYTHONPATH=src python benchmarks/bench_round.py`` (add ``--smoke``
for the CI-sized instant version; ``--dim/--clients/--reps`` to scale;
``--nested`` for the pod×data staged round and its DCI-wire split;
``--cohorts B`` caps the multi-tenant ``batched_round`` section — B
cohorts as one launch vs a B-sequential loop, host and 8-device).
The JSON lands at the repo root so every future PR diffs against it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# 8 fake host devices for the device-round section — must precede the jax
# import, so importing this module from an already-running jax process
# (benchmarks/run.py) skips the device section instead of forcing flags.
if "jax" not in sys.modules and "xla_force_host_platform_device_count" not \
        in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICES = 8

ALG_NAMES = ["sia", "re_sia", "cl_sia", "tc_sia", "cl_tc_sia"]


def vector_passes(kind: str, fused: bool) -> int:
    """HBM sweeps per node step under the counting rule in the docstring."""
    cl = kind in ("cl_sia", "cl_tc_sia")
    tc = kind in ("tc_sia", "cl_tc_sia")
    materialize = 1
    sparsifier = 3
    select_ef = 1 if fused else 2
    combine = 0 if cl else 1
    counts = 0 if fused else (2 if tc else 1)
    return materialize + sparsifier + select_ef + combine + counts


def _timed(fn, reps: int):
    out = jax.block_until_ready(fn())          # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6       # µs


def _plans(k: int):
    from repro.agg import compile_plan
    from repro.topo import graph as tg
    from repro.topo.routing import shortest_path_tree
    tree = shortest_path_tree(tg.grid_graph(2, k // 2))
    pad = (max(k, tree.max_depth() + 1), max(1, k // 2))
    return {"chain": compile_plan(k, pad_to=pad),
            "tree": compile_plan(tree, pad_to=pad)}


def _round_inputs(k: int, d: int):
    g = jax.random.normal(jax.random.PRNGKey(0), (k, d))
    e = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (k, d))
    w = jnp.ones((k,), jnp.float32)
    return g, e, w


def _cfg(name: str, q: int, impl: str, kernel_mode: str = "auto", **extra):
    from repro.core.algorithms import AggConfig, AggKind
    return AggConfig(kind=AggKind(name), q=q, topq_impl=impl,
                     kernel_mode=kernel_mode, **extra)


def _gmask(cfg, d):
    from repro.core.algorithms import AggKind
    if cfg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        return jnp.zeros((d,)).at[jnp.arange(cfg.q_global)].set(1.0)
    return None


def bench_host(k, d, q, reps, impls, kernel_mode="never"):
    """µs per jitted host round, per algorithm × plan × sparsifier.

    ``kernel_mode="never"`` pins the unfused jnp baseline regardless of
    the caller's ``REPRO_PALLAS_INTERPRET`` environment — otherwise a
    shell that still exports the parity-test knob would silently record
    interpret-mode timings into the baseline JSON.
    """
    import functools
    from repro.agg import execute
    plans = _plans(k)
    g, e, w = _round_inputs(k, d)
    out = {}
    for name in ALG_NAMES:
        out[name] = {}
        for plan_name, plan in plans.items():
            out[name][plan_name] = {}
            for impl in impls:
                cfg = _cfg(name, q, impl, kernel_mode)
                fn = jax.jit(functools.partial(
                    execute, cfg, global_mask=_gmask(cfg, d)))
                out[name][plan_name][impl] = round(
                    _timed(lambda: fn(plan, g, e, w).aggregate, reps), 1)
    return out


TAU_VARIANTS = (
    # (name, topq_impl, kernel_mode, extra AggConfig kwargs)
    ("exact", "exact", "never", {}),
    ("threshold_scan", "threshold", "never", {}),
    ("threshold_hist", "threshold", "never", {"tau_impl": "hist"}),
    ("fused_operand", "threshold", "ref", {}),
)


def bench_tau_search(k, d, q, reps, hist_branch, hist_rounds):
    """µs per jitted round across the four τ-search implementations.

    * ``exact``           — ``lax.top_k`` sparsifier (the O(d log d)
      oracle the threshold path is racing).
    * ``threshold_scan``  — branch-and-bisect with per-round
      ``count_ge_sorted`` counts over the materialized operand.
    * ``threshold_hist``  — ONE joint digit histogram replaces the
      ``hist_rounds`` count sweeps; bracket integers are bit-identical
      to the scan (``tau_impl="hist"``, rounds ∈ {1, 2}).
    * ``fused_operand``   — the scan's counts consume the bisection
      operand rebuilt on the fly from the raw node inputs
      (``kernel_mode="ref"``: fused structure, jnp kernel bodies — the
      honest host number without Pallas-interpret overhead).

    Host runs every algorithm × {chain, tree}; the 8-device shard_map
    round runs every algorithm on the chain plan (the per-rank lowering
    reads ``tau_impl`` off the same config, so the hist variant there is
    one psum'd histogram instead of ``hist_rounds`` count+psum rounds).
    """
    import functools
    from repro.agg import execute, execute_sharded
    from repro.agg.device import client_mesh
    plans = _plans(k)
    g, e, w = _round_inputs(k, d)

    def cfgs(name):
        for vname, impl, kmode, extra in TAU_VARIANTS:
            kw = dict(extra)
            if kw.get("tau_impl") == "hist":
                kw["hist_branch"] = hist_branch
                kw["hist_rounds"] = hist_rounds
            yield vname, _cfg(name, q, impl, kmode, **kw)

    host = {}
    for name in ALG_NAMES:
        host[name] = {}
        for plan_name, plan in plans.items():
            row = {}
            for vname, cfg in cfgs(name):
                fn = jax.jit(functools.partial(
                    execute, cfg, global_mask=_gmask(cfg, d)))
                row[vname] = round(
                    _timed(lambda: fn(plan, g, e, w).aggregate, reps), 1)
            host[name][plan_name] = row

    if jax.device_count() < k:
        device = {"skipped": f"needs {k} devices, have "
                             f"{jax.device_count()}"}
    else:
        mesh = client_mesh(k)
        plan = plans["chain"]
        device = {}
        for name in ALG_NAMES:
            row = {}
            for vname, cfg in cfgs(name):
                fn = jax.jit(functools.partial(
                    execute_sharded, cfg, mesh=mesh,
                    global_mask=_gmask(cfg, d)))
                row[vname] = round(
                    _timed(lambda: fn(plan, g, e, w).aggregate, reps), 1)
            device[name] = {"chain": row}

    return {"hist_branch": hist_branch, "hist_rounds": hist_rounds,
            "host": host, "device": device}


def bench_device(k, d, q, reps):
    """µs per jitted 8-device shard_map round (client-per-rank kernel)."""
    import functools
    from repro.agg import execute_sharded
    from repro.agg.device import client_mesh
    if jax.device_count() < k:
        return {"skipped": f"needs {k} devices, have {jax.device_count()} "
                           f"(set XLA_FLAGS before importing jax)"}
    mesh = client_mesh(k)
    plans = _plans(k)
    g, e, w = _round_inputs(k, d)
    out = {}
    for name in ALG_NAMES:
        out[name] = {}
        for plan_name, plan in plans.items():
            cfg = _cfg(name, q, "exact", "never")
            fn = jax.jit(functools.partial(
                execute_sharded, cfg, mesh=mesh,
                global_mask=_gmask(cfg, d)))
            out[name][plan_name] = round(
                _timed(lambda: fn(plan, g, e, w).aggregate, reps), 1)
    return out


def bench_nested(k_pod, k_data, d, q, reps):
    """Nested (pod×data) staged round vs the flat ring on the same ranks.

    Runs the chain×chain :class:`~repro.agg.nested.NestedPlan` through
    ``run_nested_segments_local`` on a (pod, data) mesh and the flat
    rotated ring over the combined (pod, data) axis, per algorithm.
    Records per-stage §V bits — stage 1 is the scarce-link (pod-seam DCI)
    wire — plus the analytic flat-vs-staged DCI split: the flat ring
    crosses the seam K_p·K_d times per round, the staged schedule K_p
    (``core.comm_cost.dci_wire_flat_vs_nested``), so the measured stage-1
    bits are the flat ring's seam traffic ÷ K_d.
    """
    import functools
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.agg.device import run_nested_segments_local
    from repro.agg.nested import pod_ring_nested
    from repro.core import comm_cost as cc
    from repro.core.ring import RingStats, rotated_ring_local

    k = k_pod * k_data
    if jax.device_count() < k:
        return {"skipped": f"needs {k} devices, have {jax.device_count()}"}
    mesh = compat.make_mesh((k_pod, k_data), ("pod", "data"))
    n = d - d % (k * k)            # divisible by both stage segmentations
    nested = pod_ring_nested(k_pod, k_data)
    G = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    EF = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (k, n))
    PEF = jnp.zeros((k, n // k_data))
    w = jnp.float32(1.0)
    sspec = jax.tree.map(lambda _: P(), (RingStats(0., 0., 0.),
                                         RingStats(0., 0., 0.)))

    out = {"k_pod": k_pod, "k_data": k_data, "n": n, "alg": {}}
    for name in ALG_NAMES:
        cfg = _cfg(name, q, "exact", "never")
        gm = _gmask(cfg, n)

        def nested_fn(g_l, ef_l, pef_l):
            seg, ef_new, (pef_new,), sts = run_nested_segments_local(
                cfg, nested, g_l[0], ef_l[0], (pef_l[0],), w,
                axes=("data", "pod"), global_mask_local=gm)
            sts = jax.tree.map(
                lambda s: jax.lax.psum(s, ("pod", "data")), sts)
            return seg[None], ef_new[None], pef_new[None], sts

        def flat_fn(g_l, ef_l):
            seg, ef_new, st = rotated_ring_local(
                cfg, g_l[0], ef_l[0], w, axis=("pod", "data"),
                global_mask_local=gm)
            st = jax.tree.map(
                lambda s: jax.lax.psum(s, ("pod", "data")), st)
            return seg[None], ef_new[None], st

        run_n = jax.jit(compat.shard_map(
            nested_fn, mesh=mesh, in_specs=(P(("pod", "data")),) * 3,
            out_specs=(P(("pod", "data")),) * 3 + (sspec,),
            axis_names={"pod", "data"}))
        run_f = jax.jit(compat.shard_map(
            flat_fn, mesh=mesh, in_specs=(P(("pod", "data")),) * 2,
            out_specs=(P(("pod", "data")),) * 2 + (
                jax.tree.map(lambda _: P(), RingStats(0., 0., 0.)),),
            axis_names={"pod", "data"}))

        _, _, _, sts = jax.block_until_ready(run_n(G, EF, PEF))
        _, _, st_f = jax.block_until_ready(run_f(G, EF))
        out["alg"][name] = {
            "nested_round_us": round(
                _timed(lambda: run_n(G, EF, PEF)[0], reps), 1),
            "flat_round_us": round(
                _timed(lambda: run_f(G, EF)[0], reps), 1),
            "stage_bits": [float(sts[0].bits), float(sts[1].bits)],
            "flat_bits": float(st_f.bits),
            # seam traffic: the flat ring carries every hop's payload
            # across the pod seam K_p·K_d times/round, the staged
            # schedule K_p — measured stage-1 bits ARE the staged seam wire
            "dci_bits_nested": float(sts[1].bits),
            "dci_bits_flat_model": float(sts[1].bits) * k_data,
        }
    flat_m, nested_m = cc.dci_wire_flat_vs_nested(k_pod, k_data, d, q)
    out["dci_packet_model"] = {"flat": flat_m, "nested": nested_m,
                               "reduction_x": flat_m / nested_m}
    # cross-check the measured staged DCI wire against the closed-form
    # CEILING: stage 1 runs K_p segments × K_p hops per data column, each
    # carrying ≤ q CL coordinates over a sub-segment of n/(K_d·K_p). It
    # can genuinely undershoot — stage 0 already Top-Q'd the pod partials,
    # so a sub-segment's γ̃ may hold fewer than q nonzeros (that is the
    # staged schedule's second saving on top of the K_d× fewer crossings).
    seg2 = n // (k_data * k_pod)
    cap = k_data * k_pod * k_pod * q * (32 + cc.idx_bits(seg2))
    got = out["alg"]["cl_sia"]["dci_bits_nested"]
    assert 0 < got <= cap, (got, cap)
    out["dci_bits_cl_sia_cap"] = cap
    return out


def bench_batched(k, d, reps, cohort_sizes, wave_dim=512):
    """Multi-tenant batched rounds: B cohorts as ONE launch vs B sequential
    rounds, on the host executor (``execute_batched`` vs an ``execute``
    loop) and the 8-device shard_map lowering (``execute_sharded_batched``
    vs an ``execute_sharded`` loop). Records per-cohort round latency and
    aggregate rounds/s for each B, plus the speedup over the sequential
    loop, in TWO regimes:

    * ``wavefront`` (d = ``wave_dim``): per-hop payloads are small (the
      multi-hop constellation case — q ≈ d/100 compact coordinates per
      ISL hop), so the round is dominated by the launch + per-level
      collective wavefront the batched path amortizes — B cohorts cost
      one L-level wavefront instead of B. This is the headline: the
      term that dominates real multi-hop rounds shrinks ~B×.
    * ``compute`` (d = the caller's ``--dim``): per-element work
      dominates. The forced-host-device CPU backend serializes lanes and
      the B-wide working set ([K, B, d] gathers) falls out of cache, so
      batching can go *below* 1× here — recorded deliberately, so the
      crossover is visible instead of hidden by a flattering dim choice.

    Also audits the scheduler contract: cohorts are submitted through one
    :class:`repro.agg.RoundScheduler` and the trace counter must not
    exceed one jit specialization per (bucket, shape, padded-B) — the
    batched path adds zero specializations beyond the bucket set.
    """
    import functools
    from repro.agg import (CohortRound, RoundScheduler, compile_plan,
                           execute, execute_batched, execute_sharded,
                           execute_sharded_batched)
    from repro.agg.device import client_mesh
    plan = compile_plan(k)
    have_dev = jax.device_count() >= k
    mesh = client_mesh(k) if have_dev else None

    out = {"alg": "cl_sia", "plan": "chain", "regimes": {}}
    for regime, dd in (("wavefront", wave_dim), ("compute", d)):
        q = max(1, dd // 100)
        cfg = _cfg("cl_sia", q, "exact", "never")
        seq_h = jax.jit(functools.partial(execute, cfg))
        bat_h = jax.jit(functools.partial(execute_batched, cfg))
        if have_dev:
            seq_d = jax.jit(functools.partial(execute_sharded, cfg,
                                              mesh=mesh))
            bat_d = jax.jit(functools.partial(execute_sharded_batched, cfg,
                                              mesh=mesh))
        cohorts = {}
        for b in cohort_sizes:
            key = jax.random.PRNGKey(b)
            g = jax.random.normal(key, (b, k, dd))
            e = 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                        (b, k, dd))
            w = jnp.ones((b, k), jnp.float32)

            def seq_loop(fn):
                return [fn(plan, g[i], e[i], w[i]).aggregate
                        for i in range(b)]

            entry = {}
            for backend, ok, seq_fn, bat_fn in (
                    ("host", True, seq_h, bat_h),
                    ("device", have_dev,
                     seq_d if have_dev else None,
                     bat_d if have_dev else None)):
                if not ok:
                    entry[backend] = {"skipped": f"needs {k} devices"}
                    continue
                us_seq = _timed(lambda: seq_loop(seq_fn), reps)
                # the shared [L, W] plan keeps the compact wire live on
                # the batched path, same as the sequential baseline;
                # stacked [B, L, W] plans are the scheduler's business
                us_bat = _timed(lambda: bat_fn(plan, g, e, w).aggregate,
                                reps)
                entry[backend] = {
                    "sequential_us": round(us_seq, 1),
                    "batched_us": round(us_bat, 1),
                    "per_cohort_us": round(us_bat / b, 1),
                    "rounds_per_s": round(b / (us_bat * 1e-6), 1),
                    "rounds_per_s_sequential": round(b / (us_seq * 1e-6),
                                                     1),
                    "speedup_x": round(us_seq / us_bat, 2),
                }
            cohorts[str(b)] = entry
        out["regimes"][regime] = {"d": dd, "q": q, "cohorts": cohorts}

    # scheduler audit (wavefront dim): two passes over every B — the
    # second pass hits warm buckets, so traces must not grow past the
    # (bucket, shape, padded-B) set
    q = max(1, wave_dim // 100)
    cfg = _cfg("cl_sia", q, "exact", "never")
    sched = RoundScheduler(cfg)
    for rnd in range(2):
        for b in cohort_sizes:
            key = jax.random.PRNGKey(100 * rnd + b)
            g = jax.random.normal(key, (b, k, wave_dim))
            sched.submit([CohortRound(cohort_id=i, plan=plan, grads=g[i],
                                      e=0.1 * g[i],
                                      weights=jnp.ones((k,)))
                          for i in range(b)])
    sched.assert_bucket_specializations()
    out["scheduler"] = {
        "submits": 2 * len(cohort_sizes),
        "shape_buckets": sched.expected_specializations,
        "jit_traces": sched.trace_counter.count,
    }
    return out


def bench_scenario(name: str):
    """Run a fault-injection preset through the simulator and record the
    realized per-round §V bits (the curve a relay-cascade / link-flap /
    degradation scenario actually produces), plus wall-clock per round.

    The whole scenario runs inside one jit specialization (asserted), so
    the per-round wall time is an honest steady-state number — the trace
    is written to a temp file and validated like the CI smoke gate.
    """
    from repro.scenario import preset
    from repro.scenario.run import run_scenario

    spec = preset(name)
    trace = os.path.join(tempfile.gettempdir(),
                         f"bench_scenario_{name}.jsonl")
    t0 = time.perf_counter()
    curves = run_scenario(spec, backend="host", out=trace)
    wall = time.perf_counter() - t0
    assert curves["_retraces"] == 1, curves["_retraces"]
    from repro.obs import validate_trace
    assert validate_trace(trace)["errors"] == []
    compiled = curves["_scenario"]
    return {
        "preset": name, "rounds": spec.rounds,
        "clients": spec.num_clients,
        "distinct_plans": len(compiled.schedule.plans),
        "injected_events": len(compiled.events),
        "retraces": curves["_retraces"],
        "round_us": round(wall / spec.rounds * 1e6, 1),
        "bits_per_round": [round(b, 1) for b in curves["bits"]],
        "bits_total": round(float(sum(curves["bits"])), 1),
        "loss_first": round(float(curves["loss"][0]), 6),
        "loss_last": round(float(curves["loss"][-1]), 6),
    }


def smoke_fused_interpret(k, d, q):
    """Run one fused (Pallas-interpret) round per algorithm and check it
    against the unfused oracle — keeps the kernel path exercised by CI on
    machines with no TPU. Returns µs per round (interpret overhead
    included — NOT comparable to the compiled timings)."""
    import functools
    import numpy as np
    from repro.agg import execute
    plan = _plans(k)["tree"]
    g, e, w = _round_inputs(k, d)
    out = {}
    for name in ALG_NAMES:
        cfg_f = _cfg(name, q, "threshold", "always")
        cfg_u = _cfg(name, q, "threshold", "never")
        gm = _gmask(cfg_f, d)
        run_f = jax.jit(functools.partial(execute, cfg_f, global_mask=gm))
        run_u = jax.jit(functools.partial(execute, cfg_u, global_mask=gm))
        rf, ru = run_f(plan, g, e, w), run_u(plan, g, e, w)
        np.testing.assert_array_equal(np.asarray(rf.aggregate),
                                      np.asarray(ru.aggregate),
                                      err_msg=f"{name} fused != unfused")
        out[name] = round(_timed(lambda: run_f(plan, g, e, w).aggregate,
                                 1), 1)
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dim", type=int, default=1 << 15,
                    help="flat gradient length d per client")
    ap.add_argument("--clients", type=int, default=DEVICES)
    ap.add_argument("--q", type=int, default=None,
                    help="per-hop Top-Q budget (default d // 100)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instant run (CI harness check); writes to a "
                         "temp file so the recorded baseline is not "
                         "clobbered")
    ap.add_argument("--nested", action="store_true",
                    help="add the pod×data staged round (2 pods × 4 ranks "
                         "on the 8 fake devices): per-stage §V bits and "
                         "the DCI-wire reduction vs the flat ring")
    ap.add_argument("--cohorts", type=int, default=8, metavar="B",
                    help="multi-tenant batched-round section: bench B in "
                         "{1, 4, 8} up to this cap (batched single-launch "
                         "vs B-sequential, host + 8-device); 0 disables")
    ap.add_argument("--hist", action="store_true",
                    help="run the tau_search section even under --smoke "
                         "(the full run always includes it): exact vs "
                         "threshold-scan vs threshold-hist vs "
                         "fused-operand, host + 8-device")
    ap.add_argument("--hist-branch", type=int, default=64, metavar="B",
                    help="bisection branch factor for the threshold_hist "
                         "variant (<= 1024)")
    ap.add_argument("--hist-rounds", type=int, default=2,
                    help="bisection rounds for the threshold_hist variant "
                         "(1 or 2 — the joint histogram covers two)")
    ap.add_argument("--scenario", default=None, metavar="PRESET",
                    help="also run a repro.scenario preset (e.g. "
                         "relay-cascade) through the simulator and record "
                         "its realized per-round SS V bits")
    ap.add_argument("--out", default=None,
                    help="output path (default: repo-root "
                         "BENCH_agg_round.json; temp file under --smoke)")
    ap.add_argument("--trace", default=None,
                    help="also write the section wall-clock spans as a "
                         "repro.obs JSONL trace (Perfetto-exportable via "
                         "python -m repro.obs.report export)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.dim, args.reps = 2048, 1
    if args.out is None:
        args.out = (os.path.join(tempfile.gettempdir(),
                                 "BENCH_agg_round.smoke.json")
                    if args.smoke
                    else os.path.join(REPO, "BENCH_agg_round.json"))
    d, k = args.dim, args.clients
    q = args.q if args.q is not None else max(1, d // 100)

    from common import provenance
    from repro.obs.timing import PhaseTimer

    passes = {name: {"unfused": vector_passes(name, False),
                     "fused": vector_passes(name, True)}
              for name in ALG_NAMES}
    assert all(p["fused"] < p["unfused"] for p in passes.values())

    timer = PhaseTimer()
    with timer.phase("host_rounds", track="bench"):
        host_rounds = bench_host(k, d, q, args.reps, ["exact", "threshold"])
    with timer.phase("device_rounds", track="bench"):
        device_rounds = bench_device(k, d, q, args.reps)
    with timer.phase("fused_interpret", track="bench"):
        fused_interpret = smoke_fused_interpret(
            k, min(d, 4096), max(1, min(d, 4096) // 100))

    result = {
        "meta": {
            "device_count": jax.device_count(),
            "d": d, "clients": k, "q": q, "reps": args.reps,
            "smoke": bool(args.smoke),
            "repro_pallas_interpret": os.environ.get(
                "REPRO_PALLAS_INTERPRET", ""),
            **provenance(),
        },
        # The structural metric that transfers to TPU: HBM sweeps per node
        # step (memory-bound round ⇒ sweeps bound wall-time). Fused is
        # strictly smaller for every algorithm.
        "vector_passes_per_node": passes,
        "host_rounds_us": host_rounds,
        "device_rounds_us": device_rounds,
        # fused path correctness + interpret-mode smoke (see docstring)
        "fused_interpret_rounds_us": fused_interpret,
    }
    if args.hist or not args.smoke:
        with timer.phase("tau_search", track="bench"):
            result["tau_search"] = bench_tau_search(
                k, d, q, args.reps, args.hist_branch, args.hist_rounds)
    if args.cohorts:
        sizes = sorted({b for b in (1, 4, 8) if b <= args.cohorts}
                       | {args.cohorts})
        with timer.phase("batched_round", track="bench"):
            result["batched_round"] = bench_batched(k, d, args.reps, sizes)
    if args.nested:
        with timer.phase("nested_round", track="bench"):
            result["nested_round"] = bench_nested(2, 4, d, q, args.reps)
    if args.scenario:
        with timer.phase("scenario_round", track="bench"):
            result["scenario_round"] = bench_scenario(args.scenario)
    result["meta"]["phases_s"] = {name: round(secs, 4) for name, secs
                                  in timer.totals().items()}
    if args.trace:
        from repro.obs.collector import TraceCollector
        with TraceCollector(args.trace, meta=dict(result["meta"])) as col:
            for s in timer.spans:
                col.record_span(s.name, s.t0_s, s.dur_s, track=s.track,
                                args=s.args)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    for name in ALG_NAMES:
        h = result["host_rounds_us"][name]["chain"]
        print(f"round,{name},host_chain_exact_us,{h['exact']}")
        print(f"round,{name},host_chain_threshold_us,{h['threshold']}")
        print(f"round,{name},passes_unfused,{passes[name]['unfused']}")
        print(f"round,{name},passes_fused,{passes[name]['fused']}")
    if "tau_search" in result:
        for name in ALG_NAMES:
            row = result["tau_search"]["host"][name]["chain"]
            for vname, _, _, _ in TAU_VARIANTS:
                print(f"tau,{name},host_chain_{vname}_us,{row[vname]}")
    if args.cohorts:
        for regime, rg in result["batched_round"]["regimes"].items():
            for b, entry in rg["cohorts"].items():
                for backend in ("host", "device"):
                    be = entry[backend]
                    if "skipped" in be:
                        continue
                    print(f"batched,{regime},B={b},{backend}_rounds_per_s,"
                          f"{be['rounds_per_s']}")
                    print(f"batched,{regime},B={b},{backend}_speedup_x,"
                          f"{be['speedup_x']}")
    return result


if __name__ == "__main__":
    main()
