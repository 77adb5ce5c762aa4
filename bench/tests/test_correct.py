"""``correct`` holds each cell to its reference, and fails where it must.

Every cell's run is driven here on the CPU at a size a test run can hold,
with the harness's look for a chip skipped: a sound run is correct; the
control (the reference in the next lower precision put in the program's
place) reads above at least one committed limit; and a run with the timed
path broken underneath comes out not correct, once for each fault the cell
can have.

    PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                  # noqa: E402
import jax.numpy as jnp                     # noqa: E402

from bench import harness                   # noqa: E402
from bench import run                       # noqa: E402

CELLS = {"round": "mamba2-130m.round-chain8-thr",
         "sim": "paper-mnist.sim-chain28",
         "train": "mamba2-130m.train-ring1"}
SEED = 2**31 + 12345                         # wider than 32 signed bits


def tiny(which: str) -> harness.Cell:
    """The committed cell with its sizes cut to what a CPU test holds; the
    limits are the committed ones. ``train-medium`` keeps more of the model,
    where float8 matrix inputs move the worst leaf as at published widths."""
    if which == "train-medium":
        cell = harness.load_cell(CELLS["train"])
        cell.config = dict(
            cell.config, d_model=256, n_layer=2, vocab_size=8000, d_state=64,
            headdim=64, chunk_size=64,
            assumed=dict(cell.config["assumed"], padded_vocab=8192))
        cell.traffic = dict(cell.traffic, batch=2, seq=128, batch_pool=8)
        return cell
    if which == "sim":
        cell = harness.load_cell(CELLS[which],
                                 spec=harness.held_spec(CELLS[which]))
    else:
        cell = harness.load_cell(CELLS[which])
    if which == "round":
        cell.config = {"family": "logreg", "input_dim": 784,
                       "num_classes": 10}
        cell.traffic = dict(cell.traffic, segments=1)
    elif which == "sim":
        cell.config = dict(cell.config, train_samples=28 * 40)
    else:
        cell.config = dict(
            cell.config, d_model=64, n_layer=2, vocab_size=500, d_state=16,
            headdim=16, chunk_size=16,
            assumed=dict(cell.config["assumed"], padded_vocab=512))
        cell.traffic = dict(cell.traffic, batch=2, seq=32, batch_pool=8)
    return cell


def measure(cell, seconds=1.0):
    peaks = harness.load_peaks("TPU v5 lite")
    return run.measure(cell, SEED, seconds, False, jax.devices(),
                       time.perf_counter(), peaks)


def over_limit(readings: dict, limits: dict) -> list:
    return [n for n, v in readings.items() if n in limits and v > limits[n]]


@pytest.mark.parametrize("which", list(CELLS))
def test_sound_run_is_correct(which):
    out = measure(tiny(which))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("which,variant", [
    ("round", "control"), ("sim", "control"), ("train-medium", "control"),
    ("train", "half_batch")])
def test_stand_in_reads_over_a_limit(which, variant):
    cell = tiny(which)
    mod = harness.driver_module(cell.traffic["driver"])
    drv = mod.Driver(cell.config, cell.traffic, cell.limits, SEED,
                     jax.devices(), 0.5)
    drv.setup()
    harness.run_window(drv.step, 0.5, harness.CompileCounter())
    checks, _, failed, readings = drv.check((variant,))
    assert failed == 0
    assert over_limit(readings[variant], cell.limits), readings


# -- faults planted under the timed path --------------------------------------

def broken_execute(fault: str):
    from repro.agg import execute as real

    def execute(cfg, plan, grads, e, weights, **kw):
        if fault == "half_clients":
            k = grads.shape[0]
            keep = (jnp.arange(k) < k // 2).astype(grads.dtype)
            return real(cfg, plan, grads * keep[:, None] * 2.0, e, weights,
                        **kw)
        res = real(cfg, plan, grads, e, weights, **kw)
        if fault == "no_forward":
            # the hops forward nothing: the PS gets client 0's own share
            only0 = (jnp.arange(grads.shape[0]) == 0)[:, None]
            alone = real(cfg, plan, grads * only0, e * only0, weights, **kw)
            return res._replace(aggregate=alone.aggregate)
        if fault == "state_unchanged":
            return res._replace(e_new=e)
        if fault == "altered_answer":
            return res._replace(aggregate=res.aggregate.at[0].add(
                jnp.max(jnp.abs(res.aggregate))))
        raise ValueError(fault)

    return execute


@pytest.mark.parametrize("fault", ["state_unchanged", "half_clients",
                                   "no_forward", "altered_answer"])
def test_round_fault_is_not_correct(fault, monkeypatch):
    from bench.drivers import round as drv_round
    monkeypatch.setattr(drv_round, "execute", broken_execute(fault))
    out = measure(tiny("round"))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_sim_fault_is_not_correct(fault, monkeypatch):
    from bench.drivers import sim as drv_sim
    import repro.fed.simulator as simmod
    real_sim = drv_sim.Simulator
    if fault == "state_unchanged":
        class Stuck(real_sim):
            def round_fn(self):
                one = super().round_fn()

                def round_(state, plan, participate=None):
                    new, log = one(state, plan, participate)
                    return state._replace(round=new.round, rng=new.rng), log
                return round_
        monkeypatch.setattr(drv_sim, "Simulator", Stuck)
    elif fault == "half_batch":
        def half(pc, *a, **kw):
            return real_sim(dataclasses.replace(
                pc, batch_size=pc.batch_size // 2), *a, **kw)
        monkeypatch.setattr(drv_sim, "Simulator", half)
    else:
        monkeypatch.setattr(simmod, "execute",
                            broken_execute("altered_answer"))
    out = measure(tiny("sim"))
    assert not out["correct"], out["checks"]


def test_train_state_unchanged_is_not_correct(monkeypatch):
    from bench.drivers import train as drv_train
    real_build = drv_train.build_train_step

    def build(*a, **kw):
        step = real_build(*a, **kw)

        def stuck(state, batch):
            new, metrics = step(state, batch)
            return state._replace(step=new.step, opt=new.opt), metrics
        return stuck

    monkeypatch.setattr(drv_train, "build_train_step", build)
    out = measure(tiny("train"))
    assert not out["correct"], out["checks"]
