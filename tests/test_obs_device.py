"""Device-backend telemetry: traces from the shard_map lowering are
bit-identical to host traces, and the train step's telemetry flag exposes
the EF fault metrics — 8 fake devices via subprocess (see conftest)."""

SIM_DEVICE_TRACE = r"""
import dataclasses, os, tempfile
import jax, numpy as np
from repro.configs import PAPER
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import partition_iid
from repro.data.synthetic import make_synthetic_mnist
from repro.fed.simulator import Simulator
from repro.obs import TraceCollector, iter_trace, validate_trace
from repro.topo import graph as tg
from repro.topo.routing import cluster_routed

k = 8
pc = dataclasses.replace(PAPER, num_clients=k)
train = make_synthetic_mnist(jax.random.PRNGKey(0), k * 40)
fed = partition_iid(jax.random.PRNGKey(2), train, k)
cfg = AggConfig(kind=AggKind.CL_SIA, q=pc.q)
tmp = tempfile.mkdtemp()

def trace(sim, name):
    path = os.path.join(tmp, name + ".jsonl")
    sim.run(6, seed=1, collector=TraceCollector(path), flush_every=3)
    assert validate_trace(path)["errors"] == []
    assert sim.trace_counter.count == 1, sim.trace_counter.count
    return [r for r in iter_trace(path) if r["kind"] == "round"]

nt = cluster_routed(tg.grid_graph(2, 4), 2)
pairs = [
    ("flat",
     Simulator(pc, cfg, fed, local_lr=pc.lr),
     Simulator(pc, cfg, fed, local_lr=pc.lr, backend="device")),
    ("nested",
     Simulator(pc, cfg, fed, local_lr=pc.lr, nested_topology=nt),
     Simulator(pc, cfg, fed, local_lr=pc.lr, nested_topology=nt,
               backend="device")),
]
for name, host, dev in pairs:
    rh = trace(host, name + "_host")
    rd = trace(dev, name + "_dev")
    for a, b in zip(rh, rd):
        for sa, sb in zip(a["stages"], b["stages"]):
            assert sa["bits"] == sb["bits"], (name, a["round"])
            assert sa["nnz"] == sb["nnz"], (name, a["round"])
        assert a["totals"]["bits"] == b["totals"]["bits"]
    print(f"{name}: device trace bits bit-identical to host")
print("PASS")
"""


TRAIN_TELEMETRY = r"""
import os, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.optim.optimizers import OptConfig
from repro.train.state import TrainConfig
from repro.train import build_train_step, init_state, state_shardings
from repro.obs import TraceCollector, validate_trace

mesh = compat.make_mesh((4, 2), ("data", "model"))
cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, param_dtype="float32")
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1),
                 opt=OptConfig(name="adamw", lr=1e-3), q_frac=0.05,
                 agg_dtype="float32", ef_dtype="float32")

with jax.set_mesh(mesh):
    st = jax.device_put(init_state(cfg, tc, mesh, jax.random.PRNGKey(0)),
                        state_shardings(cfg, tc, mesh))
    plain = jax.jit(build_train_step(cfg, tc, mesh))
    tele = jax.jit(build_train_step(cfg, tc, mesh, telemetry=True))

    _, m0 = plain(st, dict(batch))
    assert "ef_mass" not in m0 and "ef_dead_mass" not in m0

    b = dict(batch)
    b["participate"] = jnp.asarray([1., 0., 1., 1.], jnp.float32)
    st1, m1 = tele(st, b)
    # the straggler's bank is exactly the exposed dead mass
    dead_bank = float(jnp.sum(jnp.abs(st1.ef[1])))
    np.testing.assert_allclose(float(m1["ef_dead_mass"]), dead_bank,
                               rtol=1e-6)
    assert float(m1["ef_mass"]) >= dead_bank > 0.0

    # full participation → nothing exposed
    _, m2 = tele(st, dict(batch))
    assert float(m2["ef_dead_mass"]) == 0.0

    path = os.path.join(tempfile.mkdtemp(), "train.jsonl")
    with TraceCollector(path, d=cfg.d_model, num_clients=4) as col:
        col.record_train_metrics(0, jax.device_get(m1))
    assert validate_trace(path)["errors"] == []
print("PASS")
"""


def test_device_traces_bit_identical(multidev):
    multidev(SIM_DEVICE_TRACE, devices=8)


def test_train_step_telemetry_metrics(multidev):
    multidev(TRAIN_TELEMETRY, devices=8)
