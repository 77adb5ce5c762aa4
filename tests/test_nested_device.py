"""Nested-plan device lowerings: multi-device equivalence.

Acceptance contracts of the nested-plan ISSUE:

* ``execute_nested_sharded`` (client-per-rank mesh) is **bit-exact** to
  host ``execute_nested`` for all five algorithms + dense IA, over the
  chain×chain stack and a tree×chain stack — aggregate, both EF tiers,
  per-stage §V stats — and one jit specialization serves every same-shape
  nested plan (trace counter);
* ``run_nested_segments_local`` on the (pod, data) mesh is bit-exact to
  the historic hand-composed two-stage ``rotated_ring_local`` pair on the
  chain×chain stack (``hierarchical_ring_local`` is now a thin delegate —
  tests/test_hierarchical.py runs unchanged), and bit-exact per
  (stage, segment) to the staged host reference for per-pod *different*
  trees (the traced/butterfly transport) — per-rank segments, both EF
  tiers, per-stage stats;
* ``build_train_step(topology=...)`` trains over a nested plan on a
  (pod, data, model) mesh: stage-order master layout, persistent
  ``stage_ef`` tier, ``agg_bits_relay`` < ``agg_bits``, and DENSE_IA
  nested loss == flat-ring loss (the exact-sum composition);
* ``Simulator(nested_topology=..., backend="device")`` curves match the
  host backend.
"""


CLIENTS_NESTED_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.agg.nested import pod_ring_nested, execute_nested, compile_nested
from repro.agg.device import execute_nested_sharded
from repro.core.algorithms import AggConfig, AggKind
from repro.topo.tree import AggTree, PS

K, D = 8, 97
g = jax.random.normal(jax.random.PRNGKey(0), (K, D))
e = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (K, D))
w = jnp.ones((K,), jnp.float32)
part = jnp.asarray([1, 0, 1, 1, 1, 0, 1, 1], jnp.float32)
se = (0.2 * jax.random.normal(jax.random.PRNGKey(2), (2, D)),)

from repro.agg.schedule import common_shape
chainx = pod_ring_nested(2, 4)
intra = AggTree(parent=(PS, 0, 0, 1))
treex = compile_nested([[(tuple(range(4)), intra), (tuple(range(4, 8)), None)],
                        [((0, 1), None)]])
shape = common_shape([chainx, treex])
chainx, treex = chainx.pad(shape), treex.pad(shape)
assert chainx.shape == treex.shape

ALL = [AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
       AggKind.CL_TC_SIA, AggKind.DENSE_IA]
for kind in ALL:
    cfg = AggConfig(kind=kind, q=9)
    gm = jnp.zeros((D,))
    if kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        gm = gm.at[jnp.arange(cfg.q_global)].set(1.0)
    traces = []

    @jax.jit
    def dev_round(nested, g, e, w):
        traces.append(1)                       # runs at trace time only
        return execute_nested_sharded(cfg, nested, g, e, w, stage_e=se,
                                      global_mask=gm, participate=part)

    for name, nested in [("chainxchain", chainx), ("treexchain", treex)]:
        want = execute_nested(cfg, nested, g, e, w, stage_e=se,
                              global_mask=gm, participate=part)
        got = dev_round(nested, g, e, w)
        np.testing.assert_array_equal(np.asarray(want.aggregate),
                                      np.asarray(got.aggregate),
                                      err_msg=f"{name}/{kind.value}")
        np.testing.assert_array_equal(np.asarray(want.e_new),
                                      np.asarray(got.e_new),
                                      err_msg=f"{name}/{kind.value}/ef")
        for a, b in zip(want.stage_e_new, got.stage_e_new):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name}/{kind.value}/sef")
        for field in ("bits", "nnz_out", "nnz_local"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want.stats, field)),
                np.asarray(getattr(got.stats, field)),
                err_msg=f"{name}/{kind.value}/stats.{field}")
            for a, b in zip(want.stage_stats, got.stage_stats):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, field)),
                    np.asarray(getattr(b, field)),
                    err_msg=f"{name}/{kind.value}/stage_stats.{field}")
    # one XLA executable serves every same-shape nested plan
    assert len(traces) == 1, (kind, len(traces))
    print(f"{kind.value}: nested device == host, 1 trace / 2 plans")
print("PASS")
"""


SEGMENTS_CHAIN_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core.algorithms import AggConfig, AggKind
from repro.core.ring import RingStats, rotated_ring_local
from repro.core.hierarchical import hierarchical_ring_local, HierStats
from repro.agg.nested import pod_ring_nested
from repro.agg.device import run_nested_segments_local

KP, KD, n = 2, 4, 4 * 2 * 16
mesh = compat.make_mesh((KP, KD), ("pod", "data"))
K = KP * KD
G = jax.random.normal(jax.random.PRNGKey(0), (K, n))
EF = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (K, n))
PEF = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (K, n // KD))
w = jnp.float32(1.3)
sspec = HierStats(jax.tree.map(lambda _: P(), RingStats(0., 0., 0.)),
                  jax.tree.map(lambda _: P(), RingStats(0., 0., 0.)))

for kind in (AggKind.CL_SIA, AggKind.SIA, AggKind.CL_TC_SIA):
    cfg = AggConfig(kind=kind, q=8)
    gm = None
    if kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        gm = jnp.zeros((n,)).at[::17].set(1.0)

    # the historic hand-composed two-stage rings (pre-delegate program)
    def ref_fn(g_l, ef_l, pef_l):
        seg1, ef_new, st1 = rotated_ring_local(
            cfg, g_l[0], ef_l[0], w, axis="data", global_mask_local=gm)
        mask2 = None
        if gm is not None:
            r = jax.lax.axis_index("data"); seg = n // KD
            mask2 = jax.lax.dynamic_slice(gm, (r * seg,), (seg,))
        seg2, pef_new, st2 = rotated_ring_local(
            cfg, seg1, pef_l[0], jnp.float32(1), axis="pod",
            global_mask_local=mask2)
        st = jax.tree.map(lambda s: jax.lax.psum(s, ("pod", "data")),
                          HierStats(st1, st2))
        return seg2[None], ef_new[None], pef_new[None], st

    nested = pod_ring_nested(KP, KD)
    def new_fn(g_l, ef_l, pef_l):
        seg2, ef_new, (pef_new,), (st1, st2) = run_nested_segments_local(
            cfg, nested, g_l[0], ef_l[0], (pef_l[0],), w,
            axes=("data", "pod"), global_mask_local=gm)
        st = jax.tree.map(lambda s: jax.lax.psum(s, ("pod", "data")),
                          HierStats(st1, st2))
        return seg2[None], ef_new[None], pef_new[None], st

    def hier_fn(g_l, ef_l, pef_l):
        seg2, ef_new, pef_new, st = hierarchical_ring_local(
            cfg, g_l[0], ef_l[0], pef_l[0], w, global_mask_local=gm)
        st = jax.tree.map(lambda s: jax.lax.psum(s, ("pod", "data")), st)
        return seg2[None], ef_new[None], pef_new[None], st

    def run(fn):
        return jax.jit(compat.shard_map(
            fn, mesh=mesh, in_specs=(P(("pod", "data")),) * 3,
            out_specs=(P(("pod", "data")),) * 3 + (sspec,),
            axis_names={"pod", "data"}))(G, EF, PEF)

    ref, new, hier = run(ref_fn), run(new_fn), run(hier_fn)
    for i, name in enumerate(["seg", "ef", "pef"]):
        np.testing.assert_array_equal(np.asarray(ref[i]), np.asarray(new[i]),
                                      err_msg=f"{kind.value}/{name}")
        np.testing.assert_array_equal(np.asarray(ref[i]), np.asarray(hier[i]),
                                      err_msg=f"{kind.value}/hier/{name}")
    for other in (new[3], hier[3]):
        for stage in ("intra", "inter"):
            for f in ("bits", "nnz", "err_sq"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(getattr(ref[3], stage), f)),
                    np.asarray(getattr(getattr(other, stage), f)),
                    err_msg=f"{kind.value}/{stage}/{f}")
    print(f"{kind.value}: chainxchain nested == historic two-stage rings")
print("PASS")
"""


SEGMENTS_TREE_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core.algorithms import AggConfig, AggKind
from repro.core.ring import RingStats
from repro.agg.nested import compile_nested
from repro.agg.plan import execute
from repro.agg.device import run_nested_segments_local
from repro.topo.tree import AggTree, PS

KP, KD, n = 2, 4, 4 * 2 * 12
K = KP * KD
seg1, seg2 = n // KD, n // (KD * KP)
mesh = compat.make_mesh((KP, KD), ("pod", "data"))
G = jax.random.normal(jax.random.PRNGKey(0), (K, n))
EF = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (K, n))
PEF = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (K, seg1))
w = jnp.float32(1.1)

# per-pod DIFFERENT intra trees (forces the traced/butterfly transport)
# + a tree inter stage
intra0 = AggTree(parent=(1, 2, 3, PS))
intra1 = AggTree(parent=(3, 0, 0, PS))
inter = AggTree(parent=(1, PS))
nested = compile_nested(
    [[(tuple(range(0, 4)), intra0), (tuple(range(4, 8)), intra1)],
     [((0, 1), inter)]])
assert not nested.clustered[0].uniform()
stage0_ref, stage1_ref = nested.stages

for kind in (AggKind.CL_SIA, AggKind.SIA, AggKind.CL_TC_SIA):
    cfg = AggConfig(kind=kind, q=5)
    gm = None
    if kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        gm = jnp.zeros((n,)).at[::37].set(1.0)

    def fn(g_l, ef_l, pef_l):
        s2, ef_new, (pef_new,), (st1, st2) = run_nested_segments_local(
            cfg, nested, g_l[0], ef_l[0], (pef_l[0],), w,
            axes=("data", "pod"), global_mask_local=gm)
        st = jax.tree.map(lambda s: jax.lax.psum(s, ("pod", "data")),
                          (st1, st2))
        return s2[None], ef_new[None], pef_new[None], st

    sspec = jax.tree.map(lambda _: P(),
                         (RingStats(0., 0., 0.), RingStats(0., 0., 0.)))
    seg2_dev, ef_dev, pef_dev, st_dev = jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(P(("pod", "data")),) * 3,
        out_specs=(P(("pod", "data")),) * 3 + (sspec,),
        axis_names={"pod", "data"}))(G, EF, PEF)
    seg2_dev, ef_dev, pef_dev = map(np.asarray, (seg2_dev, ef_dev, pef_dev))

    # staged host reference: stage 0 per data segment s (rotated start
    # ranks, the merged multi-sink forest through host `execute`), stage 1
    # per (s, pod sub-segment t) on the stage-0 sink partials
    bits0 = bits1 = 0.0
    for s in range(KD):
        rows = np.asarray([p * KD + ((k + s) % KD)
                           for p in range(KP) for k in range(KD)])
        lo1 = s * seg1
        gm_s = None if gm is None else gm[lo1:lo1 + seg1]
        res0 = execute(cfg, stage0_ref,
                       jnp.asarray(np.asarray(G)[rows, lo1:lo1 + seg1]),
                       jnp.asarray(np.asarray(EF)[rows, lo1:lo1 + seg1]),
                       jnp.full((K,), w), global_mask=gm_s)
        bits0 += float(jnp.sum(res0.stats.bits))
        for i, rr in enumerate(rows):
            np.testing.assert_array_equal(
                ef_dev[rr, lo1:lo1 + seg1], np.asarray(res0.e_new[i]),
                err_msg=f"{kind.value} ef s={s} i={i}")
        sinks = np.asarray(res0.aggregate)          # [KP, seg1]
        for t in range(KP):
            urows = [(u + t) % KP for u in range(KP)]
            pe_rows = np.asarray([u * KD + s for u in urows])
            gm1 = (None if gm is None
                   else gm[lo1 + t * seg2: lo1 + (t + 1) * seg2])
            res1 = execute(
                cfg, stage1_ref,
                jnp.asarray(sinks[urows, t * seg2:(t + 1) * seg2]),
                jnp.asarray(np.asarray(PEF)[pe_rows,
                                            t * seg2:(t + 1) * seg2]),
                jnp.ones((KP,)), global_mask=gm1)
            bits1 += float(jnp.sum(res1.stats.bits))
            np.testing.assert_array_equal(
                seg2_dev[t * KD + s], np.asarray(res1.aggregate),
                err_msg=f"{kind.value} agg s={s} t={t}")
            for u, rr in zip(range(KP), pe_rows):
                np.testing.assert_array_equal(
                    pef_dev[rr, t * seg2:(t + 1) * seg2],
                    np.asarray(res1.e_new[u]),
                    err_msg=f"{kind.value} pef s={s} t={t} u={u}")
    np.testing.assert_allclose(float(st_dev[0].bits), bits0, rtol=1e-6)
    np.testing.assert_allclose(float(st_dev[1].bits), bits1, rtol=1e-6)
    print(f"{kind.value}: per-pod-tree nested segments == staged host ref")
print("PASS")
"""


TRAIN_NESTED = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.launch.mesh import dp_clients, make_agg_plan
from repro.optim.optimizers import OptConfig
from repro.train.state import TrainConfig
from repro.train import build_train_step, init_state, state_shardings

# model axis size 1: two *manual* DP axes next to a >1 auto model axis is
# not covered by this test
mesh = compat.make_mesh((2, 4, 1), ("pod", "data", "model"))
assert dp_clients(mesh) == 8
cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, param_dtype="float32")
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1),
                 opt=OptConfig(name="adamw", lr=1e-3), q_frac=0.05,
                 agg_dtype="float32", ef_dtype="float32")

plan = make_agg_plan(mesh, "hierarchical")
assert plan.stage_units == (8, 2)
with jax.set_mesh(mesh):
    st = jax.device_put(
        init_state(cfg, tc, mesh, jax.random.PRNGKey(0), topology=plan),
        state_shardings(cfg, tc, mesh, topology=plan))
    step = jax.jit(build_train_step(cfg, tc, mesh, topology=plan))
    losses = []
    for _ in range(6):
        st, m = step(st, dict(batch))
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
relay, total = float(m["agg_bits_relay"]), float(m["agg_bits"])
assert 0 < relay < total, (relay, total)
assert len(st.stage_ef) == 1 and st.stage_ef[0].shape[0] == 8
assert float(jnp.sum(jnp.abs(st.stage_ef[0]))) > 0   # pod-edge EF banks
print(f"nested train converges ({losses[0]:.3f} -> {losses[-1]:.3f}); "
      f"relay/total bits {relay:.0f}/{total:.0f}")

# DENSE_IA: staged composition is the exact sum → same loss as the flat
# ring step on identical inputs
tc2 = TrainConfig(agg=AggConfig(kind=AggKind.DENSE_IA),
                  opt=OptConfig(name="sgd", lr=1e-2), q_frac=0.05,
                  agg_dtype="float32", ef_dtype="float32")
with jax.set_mesh(mesh):
    st_f = jax.device_put(init_state(cfg, tc2, mesh, jax.random.PRNGKey(0)),
                          state_shardings(cfg, tc2, mesh))
    st_n = jax.device_put(
        init_state(cfg, tc2, mesh, jax.random.PRNGKey(0), topology=plan),
        state_shardings(cfg, tc2, mesh, topology=plan))
    _, mf = jax.jit(build_train_step(cfg, tc2, mesh))(st_f, dict(batch))
    _, mn = jax.jit(build_train_step(cfg, tc2, mesh, topology=plan))(
        st_n, dict(batch))
np.testing.assert_allclose(np.asarray(mf["loss"]), np.asarray(mn["loss"]),
                           rtol=1e-6)
print("dense nested train loss == flat ring train loss")
print("PASS")
"""


SIM_NESTED = r"""
import dataclasses
import jax, numpy as np
from repro.agg import TopologySchedule, pod_ring_nested
from repro.configs import PAPER
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import partition_iid
from repro.data.synthetic import make_synthetic_mnist
from repro.fed.simulator import Simulator
from repro.topo import graph as tg
from repro.topo.routing import cluster_routed

k = 8
pc = dataclasses.replace(PAPER, num_clients=k)
train = make_synthetic_mnist(jax.random.PRNGKey(0), k * 40)
fed = partition_iid(jax.random.PRNGKey(2), train, k)

nt = cluster_routed(tg.grid_graph(2, 4), 2)
for kind in (AggKind.CL_SIA, AggKind.TC_SIA):
    cfg = AggConfig(kind=kind, q=pc.q)
    host = Simulator(pc, cfg, fed, local_lr=pc.lr,
                     nested_topology=nt).run(5, seed=1)
    dev = Simulator(pc, cfg, fed, local_lr=pc.lr, nested_topology=nt,
                    backend="device").run(5, seed=1)
    np.testing.assert_allclose(host["loss"], dev["loss"], rtol=1e-5)
    np.testing.assert_allclose(host["bits"], dev["bits"], rtol=1e-6)
    assert host["loss"][-1] < host["loss"][0]
    print(f"{kind.value}: nested device backend matches host curves")

# a schedule of nested plans (per-round re-clustering) still trains
sched = TopologySchedule.from_topologies(
    [cluster_routed(tg.grid_graph(2, 4), 2), pod_ring_nested(2, 4),
     cluster_routed(tg.walker_delta(2, 4), 2)])
out = Simulator(pc, AggConfig(kind=AggKind.CL_SIA, q=pc.q), fed,
                local_lr=pc.lr).run(6, seed=1, topology_schedule=sched)
assert out["loss"][-1] < out["loss"][0]
print("PASS")
"""


def test_nested_clients_matches_host_execute(multidev):
    """execute_nested_sharded ≡ host execute_nested, 6 algorithms ×
    chain×chain / tree×chain, one trace per shape."""
    multidev(CLIENTS_NESTED_EQUIV, devices=8)


def test_nested_segments_chainxchain_is_the_hierarchical_ring(multidev):
    """Chain×chain nested segments ≡ the historic two-stage
    rotated_ring_local composition ≡ the hierarchical_ring_local
    delegate — bitwise, both EF tiers, per-stage stats."""
    multidev(SEGMENTS_CHAIN_EQUIV, devices=8)


def test_nested_segments_tree_matches_staged_host_reference(multidev):
    """Per-pod different intra trees (butterfly transport) + tree inter
    stage ≡ the staged per-segment host reference."""
    multidev(SEGMENTS_TREE_EQUIV, devices=8)


def test_train_step_nested_topology(multidev):
    multidev(TRAIN_NESTED, devices=8)


def test_simulator_nested_topology(multidev):
    multidev(SIM_NESTED, devices=8)
