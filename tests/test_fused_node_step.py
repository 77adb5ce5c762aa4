"""Fused (Pallas) vs unfused (jnp) node steps: bit-parity in interpret mode.

Contracts (ISSUE acceptance criteria):

* with ``kernel_mode="always"`` (Pallas-interpret off-TPU) every algorithm's
  node step — scalar and whole-level — produces **the same** aggregate, EF
  rows and §V HopStats as the unfused jnp reference (``kernel_mode="never"``)
  under jit, for chain and padded tree plans, stragglers/stubs, dynamic
  per-node budgets, threshold Top-Q, and bf16 inputs;
* threshold Top-Q keeps ≥ q survivors and §V bits charge the *realized*
  support, not q (regression for the ``topq_by_threshold`` over-selection);
* the compact (values, indices) wire refuses threshold-sparsified configs
  (≥ q survivors would overflow the q wire slots and silently drop
  coordinates);
* the batched threshold bisection (2-D ``threshold_for_topq``) is bitwise
  identical per lane to the vmapped scalar bisection.

Parity is asserted under ``jax.jit`` on both sides: XLA:CPU contracts the
``w·g + e`` multiply-add into an FMA inside any jitted computation (fused
and unfused alike), while un-jitted op-by-op dispatch does not — comparing
a jitted path against an eager one shows 1-ulp FMA noise that has nothing
to do with the kernels.

Everything §V-relevant (aggregate, EF, nnz, bits) is compared **bitwise**.
``err_sq`` — the ‖e‖² float diagnostic — is compared to 1 ulp: it is a
d-term float reduction whose accumulation order XLA picks per compiled
graph, so even two unfused graphs are not guaranteed the same last bit.
"""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import compile_plan, execute, execute_batched
from repro.agg.device import _use_compact
from repro.agg.plan import EXECUTOR_LAYOUTS
from repro.core import sparsify as sp
from repro.core.algorithms import (AggConfig, AggKind, HopStats, NodeCtx,
                                   index_bits, fused_node_steps, level_step,
                                   node_step)
from repro.core.chain import run_chain
from repro.topo.tree import AggTree, PS

ALL_KINDS = [AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
             AggKind.CL_TC_SIA]
IMPLS = ["exact", "threshold"]

K, D = 7, 96
TREE = AggTree(parent=(PS, 0, 1, 1, 3, 0, 5))


def _inputs(k=K, d=D, seed=0, dtype=jnp.float32):
    g = jax.random.normal(jax.random.PRNGKey(seed), (k, d)).astype(dtype)
    e = (0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                 (k, d))).astype(dtype)
    w = jnp.ones((k,), jnp.float32)
    return g, e, w


def _pair(kind, impl="exact", q=11):
    unfused = AggConfig(kind=kind, q=q, topq_impl=impl, kernel_mode="never")
    return unfused, dataclasses.replace(unfused, kernel_mode="always")


def _gmask(cfg, d, dtype=jnp.float32):
    if cfg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        m = jnp.zeros((d,)).at[jnp.arange(cfg.q_global)].set(1.0)
        return m.astype(dtype)
    return None


def _assert_same_stats(a, b, msg=""):
    for field in ("nnz_out", "nnz_global", "nnz_local", "bits"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)),
                                      err_msg=f"{msg}/stats.{field}")
    np.testing.assert_allclose(np.asarray(a.err_sq), np.asarray(b.err_sq),
                               rtol=1e-6, err_msg=f"{msg}/stats.err_sq")


def _assert_same_round(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.aggregate, np.float32),
                                  np.asarray(b.aggregate, np.float32),
                                  err_msg=f"{msg}/aggregate")
    np.testing.assert_array_equal(np.asarray(a.e_new, np.float32),
                                  np.asarray(b.e_new, np.float32),
                                  err_msg=f"{msg}/e_new")
    _assert_same_stats(a.stats, b.stats, msg)


# ---------------------------------------------------------------------------
# Scalar node_step parity (the chain / register-ring / clients-kernel path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_scalar_step_parity(kind, impl):
    cfg_u, cfg_f = _pair(kind, impl)
    g, e, _ = _inputs(k=1)
    gin = jax.random.normal(jax.random.PRNGKey(7), (D,)) * (
        jax.random.uniform(jax.random.PRNGKey(8), (D,)) < 0.1)
    gm = _gmask(cfg_u, D)
    gm = jnp.zeros((D,)) if gm is None else gm
    for p in (1.0, 0.0):
        ctx = NodeCtx(global_mask=gm, participate=jnp.float32(p))
        ru = jax.jit(lambda: node_step(cfg_u)(cfg_u, g[0], gin, e[0],
                                              jnp.float32(1.3), ctx))()
        rf = jax.jit(lambda: node_step(cfg_f)(cfg_f, g[0], gin, e[0],
                                              jnp.float32(1.3), ctx))()
        np.testing.assert_array_equal(np.asarray(ru[0]), np.asarray(rf[0]),
                                      err_msg=f"p={p}/gamma")
        np.testing.assert_array_equal(np.asarray(ru[1]), np.asarray(rf[1]),
                                      err_msg=f"p={p}/e")
        _assert_same_stats(ru[2], rf[2], f"p={p}")


# ---------------------------------------------------------------------------
# Whole-round parity through execute (level_step batched path)
# ---------------------------------------------------------------------------

# more than one kernel block with a non-zero pad: the fused round carries
# [·, 3, 8, 1024] blocks whose last 8,115 entries per row are padding
D_BLOCKS = 2 * 8192 + 77


@pytest.mark.parametrize("kind,impl,d", [
    pytest.param(kind, impl, d, id=f"{kind.value}-{impl}" + (
        "" if d == D else f"-d{d}"))
    for d in (D, D_BLOCKS) for kind in ALL_KINDS for impl in IMPLS])
def test_execute_round_parity_chain_and_padded_tree(kind, impl, d):
    cfg_u, cfg_f = _pair(kind, impl, q=11 if d == D else d // 100)
    g, e, w = _inputs(d=d, seed=2)
    gm = _gmask(cfg_u, d)
    part = jnp.asarray([1, 0, 1, 1, 0, 1, 1], jnp.float32)
    for name, topo, pad in [("chain", K, None), ("tree", TREE, (K, 4))]:
        plan = compile_plan(topo, pad_to=pad)
        for pname, p in [("all", None), ("stragglers", part)]:
            run_u = jax.jit(functools.partial(execute, cfg_u,
                                              global_mask=gm,
                                              participate=p))
            run_f = jax.jit(functools.partial(execute, cfg_f,
                                              global_mask=gm,
                                              participate=p))
            _assert_same_round(run_u(plan, g, e, w), run_f(plan, g, e, w),
                               f"{kind.value}/{impl}/{name}/{pname}")


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_execute_round_parity_dynamic_budgets(kind):
    cfg_u, cfg_f = _pair(kind)
    g, e, w = _inputs(seed=3)
    gm = _gmask(cfg_u, D)
    qb = np.asarray([5, 3, 5, 2, 5, 1, 4], np.int32)
    plan = compile_plan(TREE, q_budget=qb, pad_to=(K, 3))
    run_u = jax.jit(functools.partial(execute, cfg_u, global_mask=gm))
    run_f = jax.jit(functools.partial(execute, cfg_f, global_mask=gm))
    _assert_same_round(run_u(plan, g, e, w), run_f(plan, g, e, w),
                       f"{kind.value}/q_budget")


def test_execute_batched_rows_match_blocked_rounds():
    """``execute_batched`` keeps its [B, K, d] rows (each kernel call pads
    them) and matches, cohort by cohort, ``execute``'s blocked round and
    the jnp path, at a d of several kernel blocks on the padded tree."""
    cfg_u, cfg_f = _pair(AggKind.CL_TC_SIA, "threshold", q=D_BLOCKS // 100)
    cohorts = [_inputs(d=D_BLOCKS, seed=20 + i) for i in range(2)]
    g, e, w = (jnp.stack(x) for x in zip(*cohorts))
    gm = jnp.stack([_gmask(cfg_u, D_BLOCKS)] * 2)
    part = jnp.asarray([[1, 0, 1, 1, 0, 1, 1], [1, 1, 1, 0, 1, 1, 1]],
                       jnp.float32)
    plan = compile_plan(TREE, pad_to=(K, 4))
    before = EXECUTOR_LAYOUTS.copy()
    got = jax.jit(functools.partial(execute_batched, cfg_f, global_mask=gm,
                                    participate=part))(plan, g, e, w)
    assert EXECUTOR_LAYOUTS == before        # no execute inside
    want = jax.jit(functools.partial(execute_batched, cfg_u, global_mask=gm,
                                     participate=part))(plan, g, e, w)
    _assert_same_round(want, got, "batched")
    run = jax.jit(lambda plan, g, e, w, m, p: execute(
        cfg_f, plan, g, e, w, global_mask=m, participate=p))
    for i in range(2):
        one = run(plan, g[i], e[i], w[i], gm[i], part[i])
        _assert_same_round(jax.tree.map(lambda x: x[i], got), one,
                           f"cohort {i}")
    assert EXECUTOR_LAYOUTS - before == {("blocked", 4, D_BLOCKS): 1}


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_execute_round_parity_bf16(kind):
    """bf16 inputs promote to f32 on both paths — parity holds bitwise."""
    cfg_u, cfg_f = _pair(kind)
    g, e, w = _inputs(seed=4, dtype=jnp.bfloat16)
    gm = _gmask(cfg_u, D, jnp.bfloat16)
    plan = compile_plan(K)
    run_u = jax.jit(functools.partial(execute, cfg_u, global_mask=gm))
    run_f = jax.jit(functools.partial(execute, cfg_f, global_mask=gm))
    _assert_same_round(run_u(plan, g, e, w), run_f(plan, g, e, w),
                       f"{kind.value}/bf16")


def test_stranded_stub_plan_parity():
    """A plan with a dead stub (alive=0) folds into participate on both
    paths identically."""
    cfg_u, cfg_f = _pair(AggKind.CL_SIA)
    g, e, w = _inputs(seed=5)
    base = compile_plan(TREE)
    alive = np.ones((K,), np.float32)
    alive[4] = 0.0
    plan = dataclasses.replace(base, alive=alive)
    run_u = jax.jit(functools.partial(execute, cfg_u))
    run_f = jax.jit(functools.partial(execute, cfg_f))
    _assert_same_round(run_u(plan, g, e, w), run_f(plan, g, e, w), "stub")


def test_level_step_unfused_is_vmapped_node_step():
    """kernel_mode='never' level_step ≡ the historic vmap of node_step."""
    cfg = AggConfig(kind=AggKind.CL_SIA, q=9, kernel_mode="never")
    g, e, w = _inputs(k=4, seed=6)
    gin = jnp.zeros_like(g)
    gm = jnp.zeros((D,))
    p = jnp.asarray([1, 1, 0, 1], jnp.float32)
    got = level_step(cfg)(g, gin, e, w, p, gm)
    step = node_step(cfg)

    def one(g_r, gin_r, e_r, w_r, p_r):
        return step(cfg, g_r, gin_r, e_r, w_r,
                    NodeCtx(global_mask=gm, participate=p_r))

    want = jax.vmap(one)(g, gin, e, w, p)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(got[1]))


def test_fused_gate_trace_time():
    """The dispatch decision is static: off by default off-TPU (unless the
    REPRO_PALLAS_INTERPRET=1 CI knob forces interpret mode), on under
    kernel_mode='always', off again for an all-bf16 operand set."""
    import os
    cfg = AggConfig(kind=AggKind.CL_SIA, q=9)
    auto_on = (jax.default_backend() == "tpu"
               or os.environ.get("REPRO_PALLAS_INTERPRET") == "1")
    assert fused_node_steps(cfg) == auto_on
    cfg_f = dataclasses.replace(cfg, kernel_mode="always")
    assert fused_node_steps(cfg_f)
    g = jnp.zeros((4, D), jnp.bfloat16)
    w16 = jnp.ones((4,), jnp.bfloat16)
    assert not fused_node_steps(cfg_f, w16, g, g, g)   # bf16 compute dtype
    w32 = jnp.ones((4,), jnp.float32)
    assert fused_node_steps(cfg_f, w32, g, g, g)       # promotes to f32


def test_executor_layout_follows_the_kernel_path(monkeypatch):
    """At the round cell's shape (a K = 8 chain over 16,132,152 entries)
    ``execute`` carries the round in blocks when the levels run the Pallas
    kernels and in rows on the jnp path: traced abstractly, nothing runs."""
    import repro.kernels.ops as kops
    k, d = 8, 16_132_152
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    args = (compile_plan(k), f32((k, d)), f32((k, d)), f32((k,)))
    cfg = AggConfig(kind=AggKind.CL_SIA, q=d // 100, topq_impl="threshold")
    seen = []
    for mode, pallas in [("auto", True), ("never", False), ("ref", False)]:
        before = EXECUTOR_LAYOUTS.copy()
        with monkeypatch.context() as mp:
            if pallas:
                mp.setattr(kops, "resolve", lambda m: (True, False))
            jax.eval_shape(functools.partial(
                execute, dataclasses.replace(cfg, kernel_mode=mode)), *args)
        seen.append(EXECUTOR_LAYOUTS - before)
    assert seen == [{("blocked", 1, d): 1}, {("rows", 1, d): 1},
                    {("rows", 1, d): 1}]


def test_one_jit_trace_serves_all_same_shape_plans_fused():
    """The fused path keeps the plan/execute jit-amortization contract."""
    from repro.topo import graph as tg
    from repro.agg import TopologySchedule
    k = 8
    sched = TopologySchedule.from_topologies(
        [tg.path_graph(k), tg.star_graph(k), tg.grid_graph(2, 4)])
    cfg = AggConfig(kind=AggKind.CL_SIA, q=9, kernel_mode="always")
    g, e, w = _inputs(k=k, seed=9)
    traces = []

    @jax.jit
    def round_step(plan, g, e, w):
        traces.append(1)
        return execute(cfg, plan, g, e, w).aggregate

    outs = [round_step(sched.plan_at(r), g, e, w) for r in range(6)]
    assert len(traces) == 1
    assert all(o.shape == (D,) for o in outs)


# ---------------------------------------------------------------------------
# Threshold Top-Q: §V accounting of the realized (≥ q) support
# ---------------------------------------------------------------------------

def test_threshold_bits_charge_realized_nnz():
    """``topq_by_threshold`` keeps ≥ q survivors; HopStats must charge the
    realized support — bits == (ω+⌈log₂d⌉)·nnz_out with nnz_out ≥ q."""
    cfg = AggConfig(kind=AggKind.CL_SIA, q=11, topq_impl="threshold")
    g, e, w = _inputs(seed=10)
    res = run_chain(cfg, g, e, w)
    nnz = np.asarray(res.stats.nnz_out)
    bits = np.asarray(res.stats.bits)
    assert (nnz >= cfg.q).all(), nnz
    word = cfg.omega + index_bits(D)
    np.testing.assert_array_equal(bits, (word * nnz).astype(np.float32))

    # single-hop cross-check against the realized mask of the transmitted γ
    res1 = run_chain(cfg, g[:1], e[:1], w[:1])
    realized = int(jnp.sum(res1.aggregate != 0))
    assert realized >= cfg.q
    assert int(res1.stats.nnz_out[0]) == realized
    assert float(res1.stats.bits[0]) == word * realized


def test_threshold_bits_parity_fused():
    """Fused threshold rounds report the same realized-support bits."""
    cfg_u, cfg_f = _pair(AggKind.CL_SIA, "threshold")
    g, e, w = _inputs(seed=11)
    plan = compile_plan(K)
    ru = jax.jit(functools.partial(execute, cfg_u))(plan, g, e, w)
    rf = jax.jit(functools.partial(execute, cfg_f))(plan, g, e, w)
    np.testing.assert_array_equal(np.asarray(ru.stats.bits),
                                  np.asarray(rf.stats.bits))
    assert (np.asarray(ru.stats.nnz_out) >= cfg_u.q).all()


def test_kernel_mode_validated():
    with pytest.raises(ValueError, match="kernel_mode"):
        AggConfig(kind=AggKind.CL_SIA, q=5, kernel_mode="interpet")


def test_compact_wire_refuses_threshold_topq():
    """≥ q survivors overflow the q compact wire slots — auto must fall
    back to dense and wire='compact' must refuse."""
    plan = compile_plan(K)
    exact = AggConfig(kind=AggKind.CL_SIA, q=9)
    thresh = dataclasses.replace(exact, topq_impl="threshold")
    assert _use_compact(exact, D, plan, False, "auto")
    assert not _use_compact(thresh, D, plan, False, "auto")
    with pytest.raises(ValueError, match="exact Top-Q"):
        _use_compact(thresh, D, plan, False, "compact")


# ---------------------------------------------------------------------------
# Batched threshold bisection ≡ vmapped scalar bisection
# ---------------------------------------------------------------------------

def test_batched_threshold_matches_vmapped_scalar():
    x = jax.random.normal(jax.random.PRNGKey(12), (5, 4096))
    for q in (3, 64, 1000):
        batched = sp.threshold_for_topq(x, q)
        scalar = jax.vmap(lambda row: sp.threshold_for_topq(row, q))(x)
        np.testing.assert_array_equal(np.asarray(batched),
                                      np.asarray(scalar))
        kept = jnp.sum(jnp.abs(x) >= batched[:, None], axis=-1)
        assert (np.asarray(kept) >= q).all()


# ---------------------------------------------------------------------------
# Exact Top-Q by the counting select: bitwise the lax.top_k path
# ---------------------------------------------------------------------------

_NEVER_SELECT = 1 << 62


def _tied_level(w_lanes, d, seed):
    """Integer-valued g and half-integer e: heavy ties at every q-th value."""
    kg, ke = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.randint(kg, (w_lanes, d), -4, 5).astype(jnp.float32)
    e = 0.5 * jax.random.randint(ke, (w_lanes, d), -1, 2).astype(jnp.float32)
    return g, e


@pytest.mark.parametrize("w_lanes", [1, 3])
@pytest.mark.parametrize("kind", [AggKind.CL_SIA, AggKind.CL_TC_SIA])
def test_fused_exact_level_select_matches_top_k(kind, w_lanes, monkeypatch):
    """Above the gate the fused exact level selects by counting; its
    aggregate, EF and HopStats equal the ``lax.top_k`` path's bitwise."""
    d = sp._SELECT_MIN_D + 37
    cfg = AggConfig(kind=kind, q=d // 100, topq_impl="exact",
                    kernel_mode="always")
    g, e = _tied_level(w_lanes, d, seed=w_lanes)
    gin = jnp.round(jnp.roll(g, 1, axis=0) * 0.5)
    w = jnp.ones((w_lanes,), jnp.float32)
    p = jnp.ones((w_lanes,), jnp.float32)
    gm = (jnp.zeros((d,)).at[::97].set(1.0) if kind == AggKind.CL_TC_SIA
          else jnp.zeros((d,)))

    def run():
        before = collections.Counter(sp.EXACT_PATHS)
        out = jax.jit(lambda *a: level_step(cfg)(*a))(g, gin, e, w, p, gm)
        got = collections.Counter(sp.EXACT_PATHS)
        got.subtract(before)
        return out, +got

    q = cfg.q if kind == AggKind.CL_SIA else cfg.q_local
    new, paths = run()
    assert paths == collections.Counter({("select", w_lanes, d, q): 1})
    monkeypatch.setattr(sp, "_SELECT_MIN_D", _NEVER_SELECT)
    old, paths = run()
    assert paths == collections.Counter({("top_k", w_lanes, d, q): 1})
    for a, b, name in [(new[0], old[0], "gout"), (new[1], old[1], "e_new")]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    for field in HopStats._fields:
        np.testing.assert_array_equal(np.asarray(getattr(new[2], field)),
                                      np.asarray(getattr(old[2], field)),
                                      err_msg=field)
    assert int(jnp.sum(new[2].nnz_local)) > 0


def _tiny_train():
    from repro.agg.device import ring_chain_plan
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import OptConfig
    from repro.train.state import TrainConfig
    from repro.train.step import build_train_step, init_state
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(name="tiny", family="ssm", num_layers=2, d_model=32,
                      num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=64,
                      ssm_state=16, ssm_headdim=16, ssm_expand=2,
                      ssm_chunk=16, ssm_conv=4, param_dtype="float32")
    tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1,
                                   topq_impl="exact"),
                     opt=OptConfig(name="adamw", lr=1e-3), q_frac=0.05,
                     agg_dtype="bfloat16", ef_dtype="bfloat16")
    plan = ring_chain_plan(1)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, 64)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with jax.set_mesh(mesh):
        state = init_state(cfg, tc, mesh, jax.random.PRNGKey(0),
                           topology=plan)

    def step():
        with jax.set_mesh(mesh):
            fn = jax.jit(build_train_step(cfg, tc, mesh, topology=plan))
            return (fn(state, batch),
                    fn.lower(state, batch).compile().as_text())
    return step


def test_train_step_select_matches_top_k(monkeypatch):
    """One CPU train step of a tiny model: with the gate lowered the exact
    CL-SIA hop selects by counting, the new state and metrics equal the
    ``lax.top_k`` step's bitwise, and no top-k, sort or scatter is left in
    the aggregation's compiled program."""
    from repro.obs import scopes
    step = _tiny_train()
    monkeypatch.setattr(sp, "_SELECT_MIN_D", 1)
    before = collections.Counter(sp.EXACT_PATHS)
    new, text = step()
    paths = collections.Counter(sp.EXACT_PATHS)
    paths.subtract(before)
    assert {k[0] for k in +paths} == {"select"}
    agg = [i for i in scopes.instructions(text)
           if scopes.AGGREGATE in i.path]
    assert agg
    assert not [i for i in agg if i.opcode in ("sort", "scatter")
                or "top_k" in i.op_name or "topk" in i.opcode.lower()]
    assert "sort(" not in text and "TopK" not in text
    monkeypatch.setattr(sp, "_SELECT_MIN_D", _NEVER_SELECT)
    old, text_old = step()
    assert "top_k" in text_old
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
