"""Device-plan lowering (repro.agg.device): multi-device equivalence.

The acceptance contracts of the device-plan ISSUE:

* ``run_plan_clients_local`` (one device per client) is **bit-exact** to
  host ``agg.execute()`` for all five algorithms over a routed tree, a
  permuted chain, and a padded ``TopologySchedule`` plan — and one jit
  specialization serves every same-shape plan (trace counter);
* ``run_plan_segments_local`` (the rotated-segment ring generalization) is
  bit-exact *per segment* to ``agg.execute()`` under the segment's client
  relabeling, with static (per-slot ppermute) and butterfly (traced plan)
  transports agreeing bitwise;
* the refactored ring reproduces the historic ``rotated_ring_local``
  outputs exactly — covered by tests/test_ring_shardmap.py, which runs
  unmodified;
* ``Simulator(backend="device")`` training curves match the host backend
  (float tolerance only: XLA fuses the identical gradient math differently
  when a shard_map consumes it);
* ``segment_budget`` §V regression: summed per-segment budgets never
  exceed the global budget (the old ``max(1, ·)`` floor inflated bits
  K-fold when ``q_total < num_segments``).
"""

import numpy as np

from repro.core.ring import segment_budget


CLIENTS_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.agg import TopologySchedule, compile_plan, execute, execute_sharded
from repro.core.algorithms import AggConfig, AggKind
from repro.topo import graph as tg
from repro.topo.routing import shortest_path_tree
from repro.topo.tree import AggTree, PS

K, D = 8, 97
g = jax.random.normal(jax.random.PRNGKey(0), (K, D))
e = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (K, D))
w = jnp.ones((K,), jnp.float32)
part = jnp.asarray([1, 0, 1, 1, 1, 0, 1, 1], jnp.float32)

tree = AggTree(parent=(PS, 0, 1, 1, 3, 0, 5, 2))
routed = shortest_path_tree(tg.grid_graph(2, 4))
order = np.asarray([3, 1, 0, 6, 4, 2, 5, 7], np.int32)
sched = TopologySchedule.from_topologies([K, routed, tree])
pad = sched.shape
topos = [("chain", K), ("perm", order), ("routed", routed), ("hand", tree)]

ALL = [AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
       AggKind.CL_TC_SIA, AggKind.DENSE_IA]
for kind in ALL:
    cfg = AggConfig(kind=kind, q=9)
    gm = jnp.zeros((D,))
    if kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        gm = gm.at[jnp.arange(cfg.q_global)].set(1.0)
    traces = []

    @jax.jit
    def dev_round(plan, g, e, w, gm, part):
        traces.append(1)                       # runs at trace time only
        return execute_sharded(cfg, plan, g, e, w, global_mask=gm,
                               participate=part)

    for name, topo in topos:
        plan = compile_plan(topo, pad_to=pad)  # one shared (L, W)
        want = execute(cfg, plan, g, e, w, global_mask=gm, participate=part)
        got = dev_round(plan, g, e, w, gm, part)
        for field in ("aggregate", "e_new"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want, field)),
                np.asarray(getattr(got, field)),
                err_msg=f"{name}/{kind.value}/{field}")
        for field in ("bits", "nnz_out", "nnz_local", "err_sq"):
            np.testing.assert_array_equal(
                np.asarray(getattr(want.stats, field)),
                np.asarray(getattr(got.stats, field)),
                err_msg=f"{name}/{kind.value}/stats.{field}")
    # one XLA executable served the whole padded schedule — the device
    # path keeps the plan/execute jit-amortization contract
    assert len(traces) == 1, (kind, len(traces))
    print(f"{kind.value}: device == host execute, 1 trace / {len(topos)} plans")

    # compact (values, indices) wire transport: traced plans default to the
    # dense segment (a straggler's forwarded γ can exceed q on trees), but
    # an all-alive no-straggler round may assert safety — still bit-exact
    if kind in (AggKind.CL_SIA, AggKind.CL_TC_SIA):
        plan = compile_plan(routed, pad_to=pad)
        want = execute(cfg, plan, g, e, w, global_mask=gm)
        got = jax.jit(lambda p, a, b, c: execute_sharded(
            cfg, p, a, b, c, global_mask=gm, wire="compact"))(plan, g, e, w)
        np.testing.assert_array_equal(np.asarray(want.aggregate),
                                      np.asarray(got.aggregate))
        np.testing.assert_array_equal(np.asarray(want.e_new),
                                      np.asarray(got.e_new))
        print(f"{kind.value}: compact wire bit-exact on the routed tree")

# dtype faithfulness: the kernel mirrors the host executor's dtypes, so
# bf16 gradients/EF stay bit-exact too
cfg = AggConfig(kind=AggKind.CL_SIA, q=9)
g16, e16 = g.astype(jnp.bfloat16), e.astype(jnp.bfloat16)
plan = compile_plan(routed, pad_to=pad)
want = execute(cfg, plan, g16, e16, w)
got = jax.jit(lambda p, a, b, c: execute_sharded(cfg, p, a, b, c))(
    plan, g16, e16, w)
np.testing.assert_array_equal(
    np.asarray(want.aggregate, np.float32), np.asarray(got.aggregate, np.float32))
np.testing.assert_array_equal(
    np.asarray(want.e_new, np.float32), np.asarray(got.e_new, np.float32))
print("bf16: device == host execute")
print("PASS")
"""


SEGMENTS_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.agg import compile_plan, execute
from repro.agg.device import run_plan_segments_local
from repro.core.ring import RingStats
from repro.core.algorithms import AggConfig, AggKind
from repro.topo.tree import AggTree, PS

K, n = 8, 8 * 48
seg = n // K
mesh = compat.make_mesh((K,), ("data",))
G = jax.random.normal(jax.random.PRNGKey(0), (K, n))
EF = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (K, n))
w = jnp.float32(1.3)

tree = AggTree(parent=(PS, 0, 1, 1, 3, 0, 5, 2))
order = np.asarray([3, 1, 0, 6, 4, 2, 5, 7], np.int32)
stats_specs = jax.tree.map(lambda _: P(), RingStats(0., 0., 0.))

for topo, name in [(tree, "tree"), (order, "perm")]:
    plan = compile_plan(topo)
    for kind in (AggKind.CL_SIA, AggKind.SIA, AggKind.CL_TC_SIA):
        cfg = AggConfig(kind=kind, q=5)
        gm = None
        if kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
            gm = jnp.zeros((n,)).at[::50].set(1.0)

        def body(g_l, ef_l, pl, transport):
            final, ef_new, st = run_plan_segments_local(
                cfg, pl, g_l[0], ef_l[0], w, axis="data",
                global_mask_local=gm, transport=transport)
            return final[None], ef_new[None], jax.tree.map(
                lambda s: jax.lax.psum(s, "data"), st)

        # traced plan → butterfly routing, one specialization per shape
        fb = jax.jit(compat.shard_map(
            lambda g_l, ef_l, pl: body(g_l, ef_l, pl, "butterfly"),
            mesh=mesh,
            in_specs=(P("data"), P("data"), jax.tree.map(lambda _: P(), plan)),
            out_specs=(P("data"), P("data"), stats_specs),
            axis_names={"data"}))
        final, ef_new, stats = fb(G, EF, plan)

        # constant plan → per-slot static ppermutes (the ring's program)
        fs = jax.jit(compat.shard_map(
            lambda g_l, ef_l: body(g_l, ef_l, plan, "static"),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data"), stats_specs),
            axis_names={"data"}))
        final_s, ef_s, stats_s = fs(G, EF)
        np.testing.assert_array_equal(np.asarray(final), np.asarray(final_s))
        np.testing.assert_array_equal(np.asarray(ef_new), np.asarray(ef_s))
        np.testing.assert_allclose(float(stats.bits), float(stats_s.bits))

        # host reference: segment s runs the plan with tree positions
        # relabeled +s — position k is played by client (k+s) mod K, the
        # "rotated start ranks" that make every link busy each level.
        bits_ref = 0.0
        for s in range(K):
            rot = [(k + s) % K for k in range(K)]
            g_s = jnp.asarray(np.asarray(G)[rot, s*seg:(s+1)*seg])
            e_s = jnp.asarray(np.asarray(EF)[rot, s*seg:(s+1)*seg])
            gm_s = None if gm is None else gm[s*seg:(s+1)*seg]
            res = execute(cfg, plan, g_s, e_s, jnp.full((K,), w),
                          global_mask=gm_s)
            np.testing.assert_array_equal(
                np.asarray(final)[s], np.asarray(res.aggregate),
                err_msg=f"{name}/{kind.value} segment {s} aggregate")
            for k in range(K):
                np.testing.assert_array_equal(
                    np.asarray(ef_new)[rot[k], s*seg:(s+1)*seg],
                    np.asarray(res.e_new[k]),
                    err_msg=f"{name}/{kind.value} segment {s} EF pos {k}")
            bits_ref += float(jnp.sum(res.stats.bits))
        np.testing.assert_allclose(float(stats.bits), bits_ref, rtol=1e-6)
        print(f"{name}/{kind.value}: segments kernel == per-segment execute")

# plan.alive (stranded stub) and q_budget are PHYSICAL-RANK properties on
# the segments kernel: rank j is dead / narrow-uplinked in every segment,
# whatever position it plays. The host reference is an all-alive plan with
# participation and budgets relabeled by the segment rotation.
import dataclasses
cfg = AggConfig(kind=AggKind.CL_SIA, q=5)
base = compile_plan(tree)
alive = np.ones((K,), np.float32); alive[5] = 0.0
qb = np.asarray([5, 3, 5, 2, 5, 1, 4, 5], np.int32)
plan = dataclasses.replace(base, alive=alive, q_budget=qb)

def body_s(g_l, ef_l):
    final, ef_new, st = run_plan_segments_local(
        cfg, plan, g_l[0], ef_l[0], w, axis="data", transport="static")
    return final[None], ef_new[None], jax.tree.map(
        lambda s: jax.lax.psum(s, "data"), st)

final, ef_new, _ = jax.jit(compat.shard_map(
    body_s, mesh=mesh, in_specs=(P("data"), P("data")),
    out_specs=(P("data"), P("data"), stats_specs),
    axis_names={"data"}))(G, EF)
for s in range(K):
    rot = [(k + s) % K for k in range(K)]
    ref_plan = dataclasses.replace(base, q_budget=qb[rot])
    res = execute(cfg, ref_plan,
                  jnp.asarray(np.asarray(G)[rot, s*seg:(s+1)*seg]),
                  jnp.asarray(np.asarray(EF)[rot, s*seg:(s+1)*seg]),
                  jnp.full((K,), w), participate=jnp.asarray(alive[rot]))
    np.testing.assert_array_equal(np.asarray(final)[s],
                                  np.asarray(res.aggregate),
                                  err_msg=f"stub/budget segment {s}")
    for k in range(K):
        np.testing.assert_array_equal(
            np.asarray(ef_new)[rot[k], s*seg:(s+1)*seg],
            np.asarray(res.e_new[k]),
            err_msg=f"stub/budget segment {s} EF pos {k}")
print("stub + q_budget: rank-indexed semantics == relabeled host reference")
print("PASS")
"""


SIM_BACKEND = r"""
import dataclasses
import jax, numpy as np
from repro.agg import TopologySchedule
from repro.configs import PAPER
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import partition_iid
from repro.data.synthetic import make_synthetic_mnist
from repro.fed.simulator import Simulator
from repro.fed.topology import TreeTopology
from repro.topo import graph as tg

k = 6
pc = dataclasses.replace(PAPER, num_clients=k)
train = make_synthetic_mnist(jax.random.PRNGKey(0), k * 40)
fed = partition_iid(jax.random.PRNGKey(2), train, k)

for kind in (AggKind.CL_SIA, AggKind.TC_SIA):
    topo = TreeTopology(tg.grid_graph(2, 3), routing="widest")
    cfg = AggConfig(kind=kind, q=pc.q)
    host = Simulator(pc, cfg, fed, local_lr=pc.lr,
                     tree_topology=topo).run(5, seed=1)
    dev = Simulator(pc, cfg, fed, local_lr=pc.lr, tree_topology=topo,
                    backend="device").run(5, seed=1)
    # float tolerance: XLA fuses the identical per-client gradient math
    # differently when a shard_map consumes it (the aggregation round
    # itself is bit-exact on identical inputs — CLIENTS_EQUIV above)
    np.testing.assert_allclose(host["loss"], dev["loss"], rtol=1e-5)
    np.testing.assert_allclose(host["bits"], dev["bits"], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(host["state"].flat_w),
                               np.asarray(dev["state"].flat_w),
                               rtol=1e-4, atol=1e-6)
    print(f"{kind.value}: device backend matches host curves")

# a time-varying schedule still trains through the device backend
sched = TopologySchedule.from_topologies(
    [tg.path_graph(k), tg.star_graph(k), tg.grid_graph(2, 3)])
out = Simulator(pc, AggConfig(kind=AggKind.CL_SIA, q=pc.q), fed,
                local_lr=pc.lr, backend="device").run(
    6, seed=1, topology_schedule=sched)
assert out["loss"][-1] < out["loss"][0]
print("PASS")
"""


TRAIN_TOPOLOGY = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.launch.mesh import dp_clients, make_agg_plan
from repro.optim.optimizers import OptConfig
from repro.topo import graph as tg
from repro.topo.tree import star_tree
from repro.train.state import TrainConfig
from repro.train import build_train_step, init_state, state_shardings

mesh = compat.make_mesh((4, 2), ("data", "model"))
assert dp_clients(mesh) == 4
cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, param_dtype="float32")
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1),
                 opt=OptConfig(name="adamw", lr=1e-3), q_frac=0.05,
                 agg_dtype="float32", ef_dtype="float32")

# the DP clients aggregate over a routed constellation tree instead of the
# ring — same 3-phase step, the tree plan lowered inside phase 2
for name, topo in [("star", star_tree(4)),
                   ("grid", tg.grid_graph(2, 2))]:
    plan = make_agg_plan(mesh, topo)
    with jax.set_mesh(mesh):
        st = jax.device_put(init_state(cfg, tc, mesh, jax.random.PRNGKey(0)),
                            state_shardings(cfg, tc, mesh))
        step = jax.jit(build_train_step(cfg, tc, mesh, topology=plan))
        losses = []
        for _ in range(5):
            st, m = step(st, dict(batch))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (name, losses)
    assert float(m["agg_bits"]) > 0
    print(f"{name}: tree-topology train step converges "
          f"(loss {losses[0]:.3f} -> {losses[-1]:.3f})")

# the default (topology=None) is still the rotated ring — identical
# metrics to the explicit ring chain plan
with jax.set_mesh(mesh):
    st0 = jax.device_put(init_state(cfg, tc, mesh, jax.random.PRNGKey(0)),
                         state_shardings(cfg, tc, mesh))
    s_ring, m_ring = jax.jit(build_train_step(cfg, tc, mesh))(st0, dict(batch))
    st0 = jax.device_put(init_state(cfg, tc, mesh, jax.random.PRNGKey(0)),
                         state_shardings(cfg, tc, mesh))
    s_plan, m_plan = jax.jit(build_train_step(
        cfg, tc, mesh, topology=make_agg_plan(mesh)))(st0, dict(batch))
np.testing.assert_array_equal(np.asarray(m_ring["loss"]),
                              np.asarray(m_plan["loss"]))
np.testing.assert_array_equal(np.asarray(s_ring.master),
                              np.asarray(s_plan.master))
print("PASS")
"""


BUDGET_ACCOUNTING = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import ring as ring_mod
from repro.core.algorithms import AggConfig, AggKind, index_bits

K, n = 8, 8 * 64
seg = n // K
mesh = compat.make_mesh((K,), ("data",))
G = jax.random.normal(jax.random.PRNGKey(0), (K, n))
EF = jnp.zeros((K, n))
w = jnp.float32(1.0)

def total_bits(q_total):
    q_seg = ring_mod.segment_budget(q_total, K)
    cfg = AggConfig(kind=AggKind.CL_SIA, q=q_seg)
    def ring_fn(g_l, ef_l):
        final, ef_new, stats = ring_mod.rotated_ring_local(
            cfg, g_l[0], ef_l[0], w, axis="data")
        return final[None], ef_new[None], jax.tree.map(
            lambda s: jax.lax.psum(s, "data"), stats)
    _, _, stats = jax.jit(compat.shard_map(
        ring_fn, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"),
                   jax.tree.map(lambda _: P(), ring_mod.RingStats(0., 0., 0.))),
        axis_names={"data"}))(G, EF)
    return float(stats.bits), q_seg

# q_total < num_segments: the old max(1, ·) floor gave every segment one
# coordinate → K·K hops · (ω+log₂seg) bits from a 5-coordinate budget.
# Clamped, nothing is transmitted.
bits, q_seg = total_bits(5)
assert q_seg == 0, q_seg
assert bits == 0.0, bits

# q_total ≥ num_segments: per-hop payload ≤ q_seg nonzeros, and the §V
# budget bound holds round-wide: K segments × K hops × q_seg coordinates.
bits, q_seg = total_bits(24)
assert q_seg == 3
cap = K * K * q_seg * (32 + index_bits(seg))
assert 0 < bits <= cap, (bits, cap)
print("PASS")
"""


def test_device_plan_matches_host_execute(multidev):
    """Routed tree / permuted chain / padded schedule plans, 5 algorithms,
    bit-exact, one jit trace for all same-shape plans."""
    multidev(CLIENTS_EQUIV, devices=8)


def test_segment_plan_matches_per_segment_execute(multidev):
    """Rotated-segment kernel ≡ per-segment host execute (both
    transports), trees and permuted chains."""
    multidev(SEGMENTS_EQUIV, devices=8)


def test_simulator_device_backend(multidev):
    multidev(SIM_BACKEND, devices=8)


def test_train_step_tree_topology(multidev):
    """build_train_step aggregates over a routed tree instead of the ring;
    topology=None stays bit-identical to the historic ring step."""
    multidev(TRAIN_TOPOLOGY, devices=8)


def test_ring_segment_budget_accounting(multidev):
    multidev(BUDGET_ACCOUNTING, devices=8)


def test_segment_budget_never_exceeds_global():
    """Regression: Σ per-segment budgets ≤ global Top-Q budget (§V)."""
    for q_total in (0, 1, 5, 7, 8, 9, 64, 1000):
        for n_seg in (1, 2, 7, 8, 64):
            q_seg = segment_budget(q_total, n_seg)
            assert q_seg * n_seg <= q_total, (q_total, n_seg, q_seg)
            # and no pathological under-use when divisible
            if q_total % n_seg == 0:
                assert q_seg * n_seg == q_total
    # q == 0 is a representable AggConfig (degenerate transmit-nothing)
    from repro.core.algorithms import AggConfig, AggKind
    cfg = AggConfig(kind=AggKind.CL_SIA, q=0)
    assert cfg.q == 0
    np.testing.assert_equal(segment_budget(5, 8), 0)
