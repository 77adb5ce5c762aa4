"""Unit tests of the benchmark's machinery (CPU only; no chip needed).

    PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, model_sizes, trace  # noqa: E402


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- loading cells by name --------------------------------------------------

@pytest.mark.parametrize("name", [w["name"] for w in spec()["workloads"]])
def test_every_cell_loads_with_its_files(name):
    cell = harness.load_cell(name)
    assert cell.traffic["driver"] in ("round", "train", "sim")
    assert harness.driver_module(cell.traffic["driver"]).Driver
    assert cell.limits, "a cell needs the limits of its compared numbers"
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_held_cell_loads_only_with_its_entries():
    name = "paper-mnist.sim-chain28"
    with pytest.raises(KeyError):
        harness.load_cell(name)
    cell = harness.load_cell(name, spec=harness.held_spec(name))
    assert cell.traffic["driver"] == "sim" and cell.limits
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "sim_round_ms"]
    assert [m["name"] for m in cell.per_layer] == ["idle_share.sim",
                                                   "mfu.sim"]


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-model.no-such-mix")


def test_peaks_table_is_keyed_by_device_kind():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


def test_refuses_to_run_without_a_tpu():
    name = spec()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", name, "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 3
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_compile_counter_ignores_cache_hits():
    c = harness.CompileCounter.__new__(harness.CompileCounter)
    c.compiles = c.hits = 0
    c._on(harness.CompileCounter.COMPILE, 1.0)
    c._on(harness.CompileCounter.COMPILE, 0.1)
    c._on(harness.CompileCounter.CACHE_HIT, 0.1)
    assert c.count == 1


# -- least-work formulas against hand counts ----------------------------------

def load_config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_flat_params_by_hand():
    c = load_config("mamba2-130m")
    # per layer: in_proj 768·3352, conv 4·1792, dt_bias/A_log/D 3·24,
    # gated norm 1536, out_proj 1536·768, pre-norm 768
    layer = 768 * 3352 + 4 * 1792 + 3 * 24 + 1536 + 1536 * 768 + 768
    assert model_sizes.flat_params(c) == 24 * layer + 50432 * 768 + 768
    assert model_sizes.flat_params(c) == 129_057_216
    assert model_sizes.flat_params(load_config("paper-mnist")) == 7850


def test_flat_params_match_the_programs_parameter_tree():
    import numpy as np
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench.drivers.train import model_config
    from repro.models.model import param_specs
    c = load_config("mamba2-130m")
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(param_specs(model_config(c))))
    assert n == model_sizes.flat_params(c)


def test_round_least_work_by_hand():
    mfu = harness.metric_reader("mfu.round").__globals__
    s = {"hops": 8, "d": 16_132_152, "entry_bytes": 4, "clients": 8}
    flops, nbytes = mfu["least_work"](s)
    assert nbytes == 20 * 8 * 16_132_152          # 2.58 GB per round
    assert flops == 4 * 8 * 16_132_152


def test_simulator_round_least_work_by_hand():
    mfu = harness.metric_reader("mfu.sim").__globals__
    s = {"hops": 28, "d": 7850, "entry_bytes": 4, "clients": 28,
         "batch": 20, "input_dim": 784, "classes": 10, "samples": 59976}
    flops, nbytes = mfu["least_work"](s)
    assert nbytes == 20 * 28 * 7850 + 4 * 784 * (28 * 20 + 59976)
    assert flops == (4 * 28 * 7850 + 28 * 20 * 2 * 2 * 7840
                     + 59976 * 2 * 7840)


def test_train_forward_flops_by_hand():
    mfu = harness.metric_reader("mfu.train").__globals__
    c = load_config("mamba2-130m")
    proj = 2 * 768 * 3352 + 2 * 1536 * 768
    ssd = 2 * 256 * 128 + 2 * 256 * 24 * 64 + 4 * 24 * 64 * 128
    want = 24 * (proj + ssd) + 2 * 50432 * 768
    assert mfu["forward_flops_per_token"](c) == want


# -- metric readers -----------------------------------------------------------

def ctx(shape, units=10, seconds=1.0, tr=None, trace_units=None):
    win = harness.Window(seconds=seconds, units=units,
                         trace_units=units if trace_units is None
                         else trace_units)
    return harness.Context(cell=None, shape=shape, window=win,
                           setup_seconds=12.5,
                           peaks=harness.load_peaks("TPU v5 lite"),
                           trace=tr)


def test_end_to_end_readers():
    c = ctx({"unit": "round"}, units=40, seconds=2.0)
    assert harness.metric_reader("round_ms")(c) == 50.0
    assert harness.metric_reader("step_ms")(c) is None
    assert harness.metric_reader("sim_round_ms")(c) is None
    assert harness.metric_reader("setup_s")(c) == 12.5
    s = ctx({"unit": "step"}, units=4, seconds=10.0)
    assert harness.metric_reader("step_ms")(s) == 2500.0
    m = ctx({"unit": "sim_round"}, units=64, seconds=0.16)
    assert harness.metric_reader("sim_round_ms")(m) == 2.5
    assert harness.metric_reader("round_ms")(m) is None


def test_trace_readers_read_nothing_without_a_trace():
    c = ctx({"unit": "round", "hops": 8, "d": 64, "entry_bytes": 4})
    for name in ("idle_share.round", "mfu.round", "node_step_roofline",
                 "tau_ms.round", "executor_ms.round"):
        assert harness.metric_reader(name)(c) is None
    m = ctx({"unit": "sim_round", "hops": 28, "d": 7850, "entry_bytes": 4,
             "clients": 28, "batch": 20, "input_dim": 784, "classes": 10,
             "samples": 59976})
    for name in ("idle_share.sim", "mfu.sim"):
        assert harness.metric_reader(name)(m) is None


# -- trace reduction on hand-made planes ----------------------------------------

def ev(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats.items()))


def plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def hlo(name, op="fusion"):
    return f"%{name} = f32[8,1024]{{1,0}} {op}(f32[8,1024]{{1,0}} %cl_fuse_x.1)"


def made_trace():
    dev = plane("/device:TPU:0", XLA_Ops=[
        ev(hlo("abs_reduce_fusion.1"), 1000, 400),
        ev(hlo("while.3", "while"), 1500, 400),    # holds the two below
        ev(hlo("cl_fuse_level_pallas.2", "custom-call"), 1500, 300),
        ev(hlo("count_ge_fused_level_pallas"), 1800, 50),
        ev(hlo("fusion.2"), 3000, 500),
        ev(hlo("fusion.3"), 9000, 100)])           # after the window
    host = plane("/host:CPU", python=[
        ev("bench_unit", 900, 2000), ev("bench_unit", 2900, 1100),
        ev("PjitFunction(execute)", 2850, 100)])
    other = plane("/device:TPU:1", XLA_Ops=[ev("fusion.9", 1000, 100)])
    return trace.reduce_planes([dev, host, other], 1)


def test_trace_reduction_busy_window_and_gaps():
    t = made_trace()
    assert t.window == (900, 4000)
    assert t.window_s == pytest.approx(3100e-9)
    # union of [1000,1400] [1500,1900] [3000,3500]: 400 + 400 + 500
    assert t.busy_s == pytest.approx(1300e-9)
    assert len(t.ops) == 5
    assert [o.name for o in t.ops][:3] == ["abs_reduce_fusion", "while",
                                           "cl_fuse_level_pallas"]
    assert t.gaps(0) == [(900, 1000), (1400, 1500), (1900, 3000),
                         (3500, 4000)]
    # self time: the while's own 50 ns, the kernels 300 + 50
    assert t.op_seconds(r"while") == pytest.approx(50e-9)
    assert t.op_seconds(r"cl_fuse|count_ge") == pytest.approx(350e-9)
    assert t.op_seconds(r"x") == 0.0        # operands do not match
    assert t.total_op_seconds() == pytest.approx(1300e-9)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(500e-9)]
    # the longest gap's middle (2450) lies in no host event but the units
    assert b["idle_gaps"][0] == ["no host event", pytest.approx(1100e-9)]
    assert t.host_label(2900) == "PjitFunction(execute)"


def test_trace_with_nothing_to_read_is_an_error():
    host = plane("/host:CPU", python=[ev("bench_unit", 0, 10)])
    with pytest.raises(ValueError):
        trace.reduce_planes([host], 1)
    with pytest.raises(ValueError):
        trace.reduce_planes([plane("/device:TPU:0", XLA_Ops=[
            ev("fusion", 0, 5)])], 1)


def test_idle_share_and_rooflines_from_a_trace():
    t = made_trace()
    shape = {"unit": "round", "hops": 1, "d": 1000, "entry_bytes": 4}
    c = ctx(shape, units=2, tr=t)
    idle = harness.metric_reader("idle_share.round")(c)
    assert idle == pytest.approx(100 * (1 - 1300 / 3100))
    tau = harness.metric_reader("tau_ms.round")(c)
    assert tau == pytest.approx(1e3 * 50e-9 / 2)
    ex = harness.metric_reader("executor_ms.round")(c)
    assert ex == pytest.approx(1e3 * (1300e-9 - 350e-9) / 2)
    roof = harness.metric_reader("node_step_roofline")(c)
    assert roof == pytest.approx(100 * (20 * 1000 * 2 / 819e9) / 300e-9)
