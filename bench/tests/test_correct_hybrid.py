"""``correct`` holds the hybrid Mamba2 / MoE / attention cell to its
reference, and fails where it must.

The cell's run is driven on the CPU at a size a test run can hold (the
configuration's widths cut, its keys and the committed limits kept), with
the harness's look for a chip skipped: a sound run is correct; each
stand-in in the program's place (the reference with float8 matrix inputs;
the reference fed half of each batch) reads above a committed limit; and a run with the model broken underneath comes
out not correct, once for each planted fault.

    PYTHONPATH=src python -m pytest -q bench/tests/test_correct_hybrid.py
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
from bench.drivers import train_hybrid      # noqa: E402

CELL = "nemotron3-nano-30b-a3b.train-ring1-s8k"
SEED = 2**31 + 12345                         # wider than 32 signed bits


def tiny() -> harness.Cell:
    cell = harness.load_cell(CELL)
    c = cell.config
    cell.config = dict(
        c, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
        ssm_state_size=16, chunk_size=16, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=16,
        n_routed_experts_held=4, experts_first=4, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=64, vocab_size=500,
        assumed=dict(c["assumed"], padded_vocab=512))
    cell.traffic = dict(cell.traffic, seq=64, batch_pool=8)
    return cell


def measure(cell, seconds=1.0):
    peaks = harness.load_peaks("TPU v5 lite")
    return run.measure(cell, SEED, seconds, False, jax.devices(),
                       time.perf_counter(), peaks)


def test_sound_run_is_correct():
    out = measure(tiny())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {c["name"] for c in out["checks"]} >= {
        "first_update_err", "change3_err", "ef1_err", "bits_err",
        "loss_err", "routed_slots_err"}


@pytest.mark.parametrize("variant", ["control", "half_batch"])
def test_stand_in_reads_over_a_limit(variant):
    cell = tiny()
    assert int(cell.traffic["batch"]) >= 2   # half a batch is a batch
    drv = train_hybrid.Driver(cell.config, cell.traffic, cell.limits, SEED,
                              jax.devices(), 0.5)
    drv.setup()
    harness.run_window(drv.step, 0.5, harness.CompileCounter())
    _, _, failed, readings = drv.check((variant,))
    assert failed == 0
    over = [n for n, v in readings[variant].items()
            if n in cell.limits and v > cell.limits[n]]
    assert over, readings


# -- faults planted in the model --------------------------------------------

def _config_fault(monkeypatch, **change):
    real = train_hybrid.model_config
    monkeypatch.setattr(train_hybrid, "model_config",
                        lambda c: dataclasses.replace(real(c), **change))


def _full_width_norm(monkeypatch):
    from repro.models import ssm
    real = ssm.gated_norm
    monkeypatch.setattr(ssm, "gated_norm",
                        lambda y, z, scale, groups, eps: real(y, z, scale, 1,
                                                              eps))


def _softmax_router(monkeypatch):
    from repro.models import moe

    def route(x, router_w, *, top_k, routed_scale):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        top, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return top / jnp.sum(top, -1, keepdims=True) * routed_scale, experts

    monkeypatch.setattr(moe, "route", route)


def _capacity_drop(monkeypatch):
    """Each held expert computes at most its even share of the slots,
    T·k/E, in slot order; the rest are dropped, and not counted."""
    from repro.models import moe
    real = moe.held_experts_ffn

    def capped(params, x, weights, experts, *, first, held, **kw):
        t, k = experts.shape
        cap = t * k // params["router"].shape[1]
        flat = experts.reshape(-1)
        onehot = (flat[:, None] == jnp.arange(params["router"].shape[1])
                  ).astype(jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, 1)
        kept = jnp.where(rank < cap, flat, -1).reshape(t, k)
        return real(params, x, weights, kept, first=first, held=held, **kw)

    monkeypatch.setattr(moe, "held_experts_ffn", capped)


FAULTS = {
    "rope_on": lambda mp: _config_fault(mp, rope=True),
    "full_width_gated_norm": _full_width_norm,
    "softmax_router": _softmax_router,
    "capacity_drop": _capacity_drop,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_model_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = measure(tiny())
    assert not out["correct"], out["checks"]


# -- sizes and readers by hand ----------------------------------------------

def test_flat_params_by_hand():
    import numpy as np
    from repro.models.model import param_specs
    c = harness.load_cell(CELL).config
    mamba = (2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688
             + 2688)
    moe = 2688 * 128 + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688
    attn = 2 * 2688 * (32 + 2) * 128 + 2688
    assert (mamba, moe, attn) == (38_744_896, 100_125_312, 23_399_040)
    want = 3 * mamba + 3 * moe + attn + 2 * 16384 * 2688 + 2688
    assert train_hybrid.flat_params(c) == want == 528_092_736
    n = sum(int(np.prod(x.shape)) for x in
            jax.tree.leaves(param_specs(train_hybrid.model_config(c))))
    assert n == want


def test_forward_flops_by_hand():
    mfu = harness.metric_reader("mfu.train_hybrid").__globals__
    c = harness.load_cell(CELL).config
    m = (2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 128 * 8 * 128
         + 2 * 128 * 64 * 64 + 4 * 64 * 64 * 128)
    a = 2 * 2688 * 36 * 128 + 2 * 4096 * 2688 + 2 * 32 * 128 * 8193
    e = 2 * 2688 * 128 + 4 * 2688 * 3712
    want = 3 * m + a + 3 * e + 2 * 16384 * 2688
    assert mfu["dense_forward_flops_per_token"](c, 8192) == want
    assert mfu["routed_flops_per_slot"](c) == 4 * 2688 * 1856


def test_hybrid_readers():
    from types import SimpleNamespace
    c = harness.load_cell(CELL).config
    shape = {"unit": "step", "tokens": 8192, "seq": 8192, "config": c,
             "routed_slots": [9000, 9300], "expert_rows": [12288, 12800]}
    win = harness.Window(seconds=1.0, units=2, trace_units=2)
    ctx = harness.Context(cell=None, shape=shape, window=win,
                          setup_seconds=1.0,
                          peaks=harness.load_peaks("TPU v5 lite"),
                          trace=SimpleNamespace(window_s=1.0, chips=1))
    waste = harness.metric_reader("expert_rows_wasted.train_hybrid")(ctx)
    assert waste == pytest.approx(100 * (25088 - 18300) / 25088)
    mfu = harness.metric_reader("mfu.train_hybrid")
    g = mfu.__globals__
    flops = 3 * (2 * g["dense_forward_flops_per_token"](c, 8192) * 8192
                 + g["routed_flops_per_slot"](c) * 18300)
    assert mfu(ctx) == pytest.approx(100 * flops / 197e12)
    assert mfu(dataclasses.replace(ctx, trace=None)) is None
