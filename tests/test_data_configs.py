"""Data pipeline + config registry tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, shape_cells
from repro.data.federated import (client_minibatch, partition_dirichlet,
                                  partition_iid)
from repro.data.synthetic import (lm_batch, make_bigram_lm,
                                  make_synthetic_mnist, sample_bigram)

# ---------------------------------------------------------------------------
# synthetic data
# ---------------------------------------------------------------------------


def test_mnist_like_shapes_and_determinism():
    d1 = make_synthetic_mnist(jax.random.PRNGKey(0), 100)
    d2 = make_synthetic_mnist(jax.random.PRNGKey(0), 100)
    assert d1.x.shape == (100, 784) and d1.y.shape == (100,)
    np.testing.assert_array_equal(np.asarray(d1.x), np.asarray(d2.x))


def test_mnist_like_templates_shared_across_splits():
    """Train/test linear separability: the regression learns templates from
    train that transfer to test (the bug class this guards: per-split
    templates)."""
    tr = make_synthetic_mnist(jax.random.PRNGKey(0), 2000)
    te = make_synthetic_mnist(jax.random.PRNGKey(9), 500)
    # nearest-template classification via per-class means from TRAIN
    means = jnp.stack([tr.x[tr.y == c].mean(0) for c in range(10)])
    pred = jnp.argmax(te.x @ means.T, axis=1)
    acc = float((pred == te.y).mean())
    assert acc > 0.8, acc


def test_bigram_has_learnable_structure():
    lm = make_bigram_lm(jax.random.PRNGKey(0), 64)
    toks = sample_bigram(lm, jax.random.PRNGKey(1), 64, 128)
    assert toks.shape == (64, 129)
    # empirical conditional entropy ≪ uniform entropy
    joint = np.zeros((64, 64))
    t = np.asarray(toks)
    for b in range(t.shape[0]):
        for i in range(t.shape[1] - 1):
            joint[t[b, i], t[b, i + 1]] += 1
    cond = joint / np.maximum(joint.sum(1, keepdims=True), 1)
    ent = -np.nansum(np.where(cond > 0, cond * np.log(cond), 0), axis=1)
    assert np.nanmean(ent) < 0.7 * np.log(64)


def test_lm_batch_shapes():
    lm = make_bigram_lm(jax.random.PRNGKey(0), 32)
    b = lm_batch(lm, jax.random.PRNGKey(1), 4, 16)
    assert b["tokens"].shape == (4, 16) and b["labels"].shape == (4, 16)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["labels"][:, :-1]))


# ---------------------------------------------------------------------------
# federated partitioning
# ---------------------------------------------------------------------------


def test_partition_iid_shapes():
    data = make_synthetic_mnist(jax.random.PRNGKey(0), 1000)
    fed = partition_iid(jax.random.PRNGKey(1), data, 7)
    assert fed.x.shape == (7, 142, 784)
    bx, by = client_minibatch(fed, jax.random.PRNGKey(2), 20)
    assert bx.shape == (7, 20, 784) and by.shape == (7, 20)


def test_partition_dirichlet_skews_labels():
    data = make_synthetic_mnist(jax.random.PRNGKey(0), 4000)
    fed = partition_dirichlet(jax.random.PRNGKey(1), data, 8, alpha=0.1)
    assert fed.x.shape[0] == 8
    # low alpha → at least one client heavily skewed toward few classes
    maxfrac = 0.0
    for k in range(8):
        counts = np.bincount(np.asarray(fed.y[k]), minlength=10)
        maxfrac = max(maxfrac, counts.max() / counts.sum())
    assert maxfrac > 0.5


# ---------------------------------------------------------------------------
# config registry
# ---------------------------------------------------------------------------


def test_registry_complete():
    assert len(ARCHS) == 11
    for a in ARCHS:
        cfg = get_config(a)
        assert cfg.name == a
        smoke = get_config(a, smoke=True)
        assert smoke.family == cfg.family


PUBLISHED_N = {  # billions, loose tolerance (head/frontend conventions vary)
    "granite-34b": (34, 0.05), "codeqwen1.5-7b": (7.25, 0.15),
    "glm4-9b": (9.4, 0.1), "phi4-mini-3.8b": (3.84, 0.1),
    "mixtral-8x7b": (46.7, 0.05), "zamba2-1.2b": (1.22, 0.1),
    "mamba2-130m": (0.13, 0.1), "musicgen-medium": (1.5, 0.25),
    "nemotron3-nano-30b-a3b": (31.6, 0.05),
}


@pytest.mark.parametrize("arch", list(PUBLISHED_N))
def test_param_counts_near_published(arch):
    n, tol = PUBLISHED_N[arch]
    got = get_config(arch).param_count() / 1e9
    assert abs(got - n) / n <= tol, (arch, got)


def test_moe_active_counts():
    mix = get_config("mixtral-8x7b")
    assert 12.0 < mix.active_param_count() / 1e9 < 14.0


def test_shape_cells_assignment():
    total = sum(len(shape_cells(get_config(a))) for a in ARCHS)
    assert total == 37  # 11×3 + 4 sub-quadratic long_500k
    assert "long_500k" in shape_cells(get_config("mamba2-130m"))
    assert "long_500k" in shape_cells(get_config("nemotron3-nano-30b-a3b"))
    assert "long_500k" in shape_cells(get_config("zamba2-1.2b"))
    assert "long_500k" in shape_cells(get_config("mixtral-8x7b"))
    assert "long_500k" not in shape_cells(get_config("granite-34b"))
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}


def test_vocab_padding():
    iv = get_config("internvl2-26b")
    assert iv.padded_vocab % 256 == 0 and iv.padded_vocab >= iv.vocab_size
    assert get_config("mixtral-8x7b").padded_vocab == 32000
