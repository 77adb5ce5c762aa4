"""Nemotron-H (hybrid_moe) against its plain reference.

The program's grouped SSD against the step-by-step recurrence; the dropless
expert layer against a masked loop over its experts, and the shares of
every expert-parallel chip against the uncut layer; the model's loss, every
leaf's gradient and one whole federated step (``build_train_step`` on a
1x1 mesh) against ``bench/refs/nemotron_h.py``, at the registry's SMOKE
size on seeded random weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.refs import nemotron_h as ref            # noqa: E402
from repro import compat                            # noqa: E402
from repro.agg.device import ring_chain_plan        # noqa: E402
from repro.configs import get_config                # noqa: E402
from repro.core.algorithms import AggConfig, AggKind  # noqa: E402
from repro.models import model, moe, ssm            # noqa: E402
from repro.optim.optimizers import OptConfig        # noqa: E402
from repro.train.state import TrainConfig           # noqa: E402
from repro.train.step import build_train_step, init_state  # noqa: E402

CFG = get_config("nemotron3-nano-30b-a3b", smoke=True)


def _dims(cfg, seq):
    return {"pattern": cfg.layer_pattern, "di": cfg.d_inner,
            "h": cfg.ssm_heads, "p": cfg.ssm_headdim, "g": cfg.ssm_groups,
            "n": cfg.ssm_state, "hq": cfg.num_heads, "hkv": cfg.num_kv_heads,
            "dh": cfg.resolved_head_dim, "held": cfg.held_experts,
            "first": cfg.experts_first, "topk": cfg.num_experts_per_tok,
            "scale": cfg.routed_scale, "vocab": cfg.vocab_size,
            "eps": cfg.norm_eps, "block": min(8, seq)}


def _random_params(cfg, seed=0):
    """The program's parameter tree with every leaf drawn at random (biases
    and norms too), small enough for a sound forward pass."""
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        noise = jax.random.normal(k, leaf.shape, jnp.float32)
        out.append((leaf + 0.1 * noise if leaf.ndim == 1
                    else 0.3 * noise / np.sqrt(leaf.shape[-2])
                    ).astype(leaf.dtype))
    return jax.tree.unflatten(tree, out)


def _tokens(cfg, b, s, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                           cfg.vocab_size)
    return t[:, :-1], t[:, 1:]


# ---------------------------------------------------------------------------
# Grouped SSD
# ---------------------------------------------------------------------------

def _ssd_inputs(g, seed=0, b=2, l=32, h=8, p=4, n=6):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, h)))
    a_log = 0.3 * jax.random.normal(k[2], (h,))
    bb = jax.random.normal(k[3], (b, l, g, n))
    cc = jax.random.normal(k[4], (b, l, g, n))
    return x, dt, a_log, bb, cc


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_grouped_ssd_matches_the_recurrence(groups):
    x, dt, a_log, b, c = _ssd_inputs(groups)
    y, s = ssm.ssd_chunked(x, dt, a_log, b, c, 8)
    y_n, s_n = ssm.naive_ssd(x, dt, a_log, b, c)
    np.testing.assert_allclose(y, y_n, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, s_n, rtol=1e-4, atol=1e-4)


def test_one_group_is_the_single_group_program():
    x, dt, a_log, b, c = _ssd_inputs(1, seed=3)
    grouped = ssm.ssd_chunked(x, dt, a_log, b, c, 8)
    single = ssm.ssd_chunked(x, dt, a_log, b[:, :, 0], c[:, :, 0], 8)
    for a, w in zip(grouped, single):
        assert np.array_equal(np.asarray(a), np.asarray(w))


def test_gated_norm_normalises_each_group_alone():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    y = jax.random.normal(k1, (3, 12))
    z = jax.random.normal(k2, (3, 12))
    scale = jax.random.normal(k3, (12,))
    got = ssm.gated_norm(y, z, scale, 3, 1e-5)
    yz = (y * jax.nn.silu(z)).reshape(3, 3, 4)
    want = (yz / jnp.sqrt(jnp.mean(yz * yz, -1, keepdims=True) + 1e-5)
            ).reshape(3, 12) * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# The dropless expert share
# ---------------------------------------------------------------------------

def _moe_params(e=16, d=24, f=20, fs=28, seed=5):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": jax.random.normal(k[0], (d, e)) / np.sqrt(d),
            "w_up": jax.random.normal(k[1], (e, d, f)) / np.sqrt(d),
            "w_down": jax.random.normal(k[2], (e, f, d)) / np.sqrt(f),
            "shared_up": jax.random.normal(k[3], (d, fs)) / np.sqrt(d),
            "shared_down": jax.random.normal(k[4], (fs, d)) / np.sqrt(fs)}


def _masked_loop(p, x, w, idx, first, held):
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(held):
        we = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        h = jnp.square(jax.nn.relu(x @ p["w_up"][j]))
        y = y + we[:, None] * (h @ p["w_down"][j])
    return y


def _share(p, first, held):
    return dict(p, w_up=p["w_up"][first:first + held],
                w_down=p["w_down"][first:first + held])


def _force_dispatch(monkeypatch, dispatch):
    """The expert layer takes the blocks up to a size, then sorts."""
    most = {"blocks": 1 << 62, "sorted": 0}[dispatch]
    monkeypatch.setattr(moe, "BLOCKS_MAX_ENTRIES", most)


@pytest.mark.parametrize("dispatch", ["blocks", "sorted"])
@pytest.mark.parametrize("skewed", [False, True])
def test_held_experts_compute_every_routed_slot(skewed, dispatch,
                                                monkeypatch):
    _force_dispatch(monkeypatch, dispatch)
    p = _moe_params()
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 24))
    if skewed:          # every token routes to expert 4: no capacity
        p = dict(p, router=p["router"].at[0, 4].set(50.0))
        x = x.at[:, 0].set(1.0)
    w, idx = moe.route(x, p["router"], top_k=6, routed_scale=2.5)
    share = _share(p, 4, 4)
    y, counters = moe.held_experts_ffn(share, x, w, idx, first=4, held=4)
    np.testing.assert_allclose(y, _masked_loop(share, x, w, idx, 4, 4),
                               rtol=2e-5, atol=2e-5)
    local = np.asarray(idx) - 4
    per_expert = [(local == j).sum() for j in range(4)]
    assert int(counters["routed_slots"]) == sum(per_expert)
    # blocks: every token through every held expert; sorted: the slots
    want_rows = 4 * 40 if dispatch == "blocks" else sum(per_expert)
    assert int(counters["expert_rows"]) == want_rows
    if skewed:
        assert per_expert[0] == 40


def test_expert_dispatches_give_the_same_gradients(monkeypatch):
    p = _share(_moe_params(), 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 24))
    w, idx = moe.route(x, _moe_params()["router"], top_k=6,
                       routed_scale=2.5)
    c = jax.random.normal(jax.random.PRNGKey(9), (40, 24))

    def grads(dispatch):
        _force_dispatch(monkeypatch, dispatch)

        def f(p, x, w):
            y, _ = moe.held_experts_ffn(p, x, w, idx, first=4, held=4)
            return jnp.sum(y * c)
        return jax.grad(f, argnums=(0, 1, 2))(p, x, w)

    for a, b in zip(jax.tree.leaves(grads("blocks")),
                    jax.tree.leaves(grads("sorted"))):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_expert_dispatch_follows_the_blocks_size(monkeypatch):
    p = _share(_moe_params(), 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 24))
    w, idx = moe.route(x, _moe_params()["router"], top_k=6,
                       routed_scale=2.5)
    f = p["w_up"].shape[-1]
    rows = {}
    for most in (4 * 40 * f, 4 * 40 * f - 1):
        monkeypatch.setattr(moe, "BLOCKS_MAX_ENTRIES", most)
        _, counters = moe.held_experts_ffn(p, x, w, idx, first=4, held=4)
        rows[most] = int(counters["expert_rows"])
    assert rows[4 * 40 * f] == 4 * 40                        # blocks
    assert rows[4 * 40 * f - 1] == int(counters["routed_slots"])  # sorted


def test_expert_shares_sum_to_the_uncut_layer():
    """The routed parts of all E / E_held shares, plus the shared expert
    counted once, give the layer that holds every expert."""
    p = _moe_params()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 20, 24))
    full, counters = moe.moe_dropless(p, x, top_k=6, first=0, held=16,
                                      routed_scale=2.5)
    xt = x.reshape(40, 24)
    w, idx = moe.route(xt, p["router"], top_k=6, routed_scale=2.5)
    parts = [moe.held_experts_ffn(_share(p, s, 4), xt, w, idx, first=s,
                                  held=4) for s in range(0, 16, 4)]
    shared = jnp.square(jax.nn.relu(xt @ p["shared_up"])) @ p["shared_down"]
    total = sum(y for y, _ in parts) + shared
    np.testing.assert_allclose(full.reshape(40, 24), total, rtol=1e-5,
                               atol=1e-5)
    assert sum(int(c["routed_slots"]) for _, c in parts) == 40 * 6
    assert int(counters["routed_slots"]) == 40 * 6


# ---------------------------------------------------------------------------
# The model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dispatch", ["blocks", "sorted"])
def test_loss_and_every_gradient_match_the_reference(dispatch, monkeypatch):
    _force_dispatch(monkeypatch, dispatch)
    params = _random_params(CFG)
    tokens, labels = _tokens(CFG, 2, 16)
    batch = {"tokens": tokens, "labels": labels}
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss_fn(CFG, p, batch), has_aux=True)(params)
    want_loss, want_grads, want_slots = ref.loss_and_grad(
        params, np.asarray(tokens), np.asarray(labels), dims=_dims(CFG, 16))
    np.testing.assert_allclose(float(loss), want_loss, rtol=1e-5)
    assert int(metrics["counters"]["routed_slots"]) == want_slots
    got = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w in zip(got, jax.tree.leaves(want_grads)):
        scale = float(jnp.linalg.norm(w)) + 1e-12
        err = float(jnp.linalg.norm(g - w)) / scale
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("ef_flat", [False, True])
def test_one_federated_step_matches_the_reference(ef_flat):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    opt = {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.0, "warmup": 10, "decay_steps": 100}
    tc = TrainConfig(
        agg=AggConfig(kind=AggKind.CL_SIA, q=1, topq_impl="exact"),
        opt=OptConfig(name="adamw", lr=opt["lr"], b1=opt["b1"],
                      b2=opt["b2"], eps=opt["eps"]),
        q_frac=0.02, agg_dtype="bfloat16", ef_dtype="bfloat16",
        lr_warmup=opt["warmup"], lr_decay_steps=opt["decay_steps"],
        ef_flat=ef_flat)
    plan = ring_chain_plan(1)
    tokens, labels = _tokens(CFG, 2, 16, seed=8)
    with jax.set_mesh(mesh):
        st = init_state(CFG, tc, mesh, jax.random.PRNGKey(0), topology=plan)
        st = st._replace(params=_random_params(CFG))
        st = st._replace(master=jnp.asarray(
            ref.host_flat(st.params, jnp.float32)))
        step = jax.jit(build_train_step(CFG, tc, mesh, topology=plan))
        new, metrics = step(st, {"tokens": tokens, "labels": labels})
    d = st.master.shape[0]
    want = ref.train(st.params, [(np.asarray(tokens), np.asarray(labels))],
                     steps=1, dims=_dims(CFG, 16), q=int(0.02 * d), opt=opt)
    np.testing.assert_allclose(float(metrics["loss"]), want["loss"][0],
                               rtol=1e-5)
    assert float(metrics["agg_bits"]) == want["bits"][0]
    assert int(metrics["routed_slots"]) == want["routed_slots"][0]

    def rel(a, b):
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    first = np.asarray(new.opt.m) / (1 - opt["b1"])
    assert rel(first, want["first"]) < 1e-2
    assert rel(np.asarray(new.master - st.master), want["change"]) < 1e-2
    ef = np.asarray(new.ef, np.float32).reshape(-1)
    assert rel(ef, want["ef1"].astype(np.float32)) < 5e-2
