"""Pallas kernels vs pure-jnp oracles: shape × dtype sweeps (interpret).

The single-node sweeps run the level kernels at W = 1 (one lane) against
the per-node oracles of :mod:`repro.kernels.ref`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [63, 1024, 8192, 8192 + 17, 65536 + 3]
DTYPES = [jnp.float32, jnp.bfloat16]


def _vec(key, d, dtype):
    return jax.random.normal(key, (d,), jnp.float32).astype(dtype)


def _one(*xs):
    """A single node as a one-lane level: [d] → [1, d], scalars → [1]."""
    return tuple(jnp.reshape(jnp.asarray(x), (1, -1) if jnp.ndim(x) else (1,))
                 for x in xs)


def _row(out):
    return tuple(o[0] for o in out)


def _tols(dtype):
    # bf16 outputs differ by one quantum when ref/kernel f32 intermediates
    # round to adjacent bf16 values
    if dtype == jnp.bfloat16:
        return dict(rtol=1e-2, atol=2e-3)
    return dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_count_ge_sweep(d, dtype):
    x = _vec(jax.random.PRNGKey(d), d, dtype)
    taus = jnp.linspace(0.01, 2.5, 32)
    np.testing.assert_array_equal(
        np.asarray(ops.count_ge_level(*_one(x, taus), mode="always")[0]),
        np.asarray(ref.ref_count_ge(x, taus)))


@pytest.mark.parametrize("d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sparsify_ef_sweep(d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(d + 1), 3)
    g = _vec(k1, d, dtype)
    e = (0.1 * jax.random.normal(k2, (d,))).astype(dtype)
    mask = (jax.random.uniform(k3, (d,)) < 0.02).astype(jnp.float32)
    w, tau = jnp.float32(1.7), jnp.float32(1.2)
    r = ref.ref_sparsify_ef(g, e, mask, w, tau)
    p = _row(ops.sparsify_ef_level(*_one(g, e, mask, w, tau, 1.0),
                                   mode="always"))
    np.testing.assert_allclose(np.asarray(r[0], np.float32),
                               np.asarray(p[0], np.float32), **_tols(dtype))
    np.testing.assert_allclose(np.asarray(r[1], np.float32),
                               np.asarray(p[1], np.float32), **_tols(dtype))
    assert abs(int(r[2]) - int(p[2])) <= (2 if dtype == jnp.bfloat16 else 0)


@pytest.mark.parametrize("d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_chain_accum_sweep(d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(d + 2))
    gamma = _vec(k1, d, dtype) * (jax.random.uniform(k2, (d,)) < 0.05)
    gbar = _vec(k2, d, dtype) * (jax.random.uniform(k1, (d,)) < 0.05)
    r = ref.ref_chain_accum(gamma, gbar)
    p = _row(ops.chain_accum_level(*_one(gamma, gbar, 1.0), mode="always"))
    np.testing.assert_allclose(np.asarray(r[0], np.float32),
                               np.asarray(p[0], np.float32), **_tols(dtype))
    assert int(r[1]) == int(p[1])


@pytest.mark.parametrize("d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_cl_fuse_sweep(d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(d + 3), 3)
    g, e, gi = (_vec(k, d, dtype) for k in ks)
    w, tau = jnp.float32(0.8), jnp.float32(1.4)
    r = ref.ref_cl_fuse(g, e, gi, w, tau)
    p = _row(ops.cl_fuse_level(*_one(g, e, gi, w, tau, 1.0, 1.0),
                               mode="always"))
    for a, b in zip(r[:2], p[:2]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **_tols(dtype))
    assert abs(int(r[2]) - int(p[2])) <= (2 if dtype == jnp.bfloat16 else 0)


def test_threshold_pipeline_with_pallas_counts():
    """End-to-end: bisection with the Pallas count kernel hits the budget."""
    from repro.core import sparsify as sp
    x = jax.random.normal(jax.random.PRNGKey(7), (50_000,))
    for q in (10, 500, 5000):
        tau = sp.threshold_for_topq(
            x, q, count_fn=lambda m, t: ops.count_ge_level(
                *_one(m, t), mode="always")[0])
        kept = int(jnp.sum(jnp.abs(x) >= tau))
        assert q <= kept <= q + max(2, int(0.02 * x.size))


def test_mode_never_uses_ref():
    x = jnp.ones((100,))
    taus = jnp.asarray([0.5, 1.5])
    out = ops.count_ge_level(*_one(x, taus), mode="never")[0]
    np.testing.assert_array_equal(np.asarray(out), [100, 0])


# ---------------------------------------------------------------------------
# Batched W-lane level kernels (repro.kernels.level)
#
# Refs are jitted: XLA:CPU contracts w·g+e into an FMA inside any compiled
# graph (interpret-mode Pallas included); an eager ref differs by 1 ulp.
# ---------------------------------------------------------------------------

LEVEL_SHAPES = [(1, 63), (3, 1024), (2, 8192 + 17), (5, 4096)]


def _level_inputs(w, d, seed=0):
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (w, d))
    e = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (w, d))
    gin = jax.random.normal(jax.random.fold_in(key, 2), (w, d)) * (
        jax.random.uniform(jax.random.fold_in(key, 3), (w, d)) < 0.05)
    mask = (jax.random.uniform(jax.random.fold_in(key, 4), (w, d))
            < 0.02).astype(jnp.float32)
    gmask = (jax.random.uniform(jax.random.fold_in(key, 5), (w, d))
             < 0.05).astype(jnp.float32)
    ws = jnp.linspace(0.5, 1.9, w)
    tau = jnp.linspace(0.6, 2.0, w)
    p = (jnp.arange(w) % 2).astype(jnp.float32)        # stragglers mixed in
    valid = jnp.where(jnp.arange(w) == w - 1, 0.0, 1.0)  # last lane padded
    return g, e, gin, mask, gmask, ws, tau, p, valid


@pytest.mark.parametrize("w,d", LEVEL_SHAPES)
def test_sparsify_ef_level_sweep(w, d):
    import functools
    g, e, gin, mask, gmask, ws, tau, p, valid = _level_inputs(w, d, d)
    for mi in (None, mask):
        r = jax.jit(functools.partial(ops.sparsify_ef_level,
                                      mode="never"))(g, e, mi, ws, tau,
                                                     valid)
        k = ops.sparsify_ef_level(g, e, mi, ws, tau, valid, mode="always")
        for a, b in zip(r, k):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # invalid (padding) lanes output zeros and count nothing
    assert not np.asarray(k[0][-1]).any()
    assert int(k[2][-1]) == 0


@pytest.mark.parametrize("w,d", LEVEL_SHAPES)
def test_chain_accum_level_sweep(w, d):
    import functools
    g, e, gin, mask, gmask, ws, tau, p, valid = _level_inputs(w, d, d + 1)
    for gm in (None, gmask):
        r = jax.jit(functools.partial(ops.chain_accum_level,
                                      mode="never"))(gin, g, valid, gm)
        k = ops.chain_accum_level(gin, g, valid, gm, mode="always")
        for a, b in zip(r, k):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # off-mask count never exceeds the total
    assert (np.asarray(k[2]) <= np.asarray(k[1])).all()


@pytest.mark.parametrize("w,d", LEVEL_SHAPES)
def test_cl_fuse_level_sweep(w, d):
    import functools
    g, e, gin, mask, gmask, ws, tau, p, valid = _level_inputs(w, d, d + 2)
    for gm in (None, gmask):
        for mi in (None, mask):
            r = jax.jit(functools.partial(
                ops.cl_fuse_level, mode="never"))(g, e, gin, ws, tau, p,
                                                  valid, gm, mi)
            k = ops.cl_fuse_level(g, e, gin, ws, tau, p, valid, gm, mi,
                                  mode="always")
            for a, b in zip(r, k):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("w,d", LEVEL_SHAPES)
def test_count_ge_level_sweep(w, d):
    key = jax.random.PRNGKey(d + 3)
    x = jax.random.normal(key, (w, d))
    taus = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                     (w, 32))) + 0.01
    np.testing.assert_array_equal(
        np.asarray(ops.count_ge_level(x, taus, mode="never")),
        np.asarray(ops.count_ge_level(x, taus, mode="always")))


def test_cl_fuse_level_straggler_semantics():
    """p=0 lanes forward γ_in unchanged and bank g̃ = w·g+e into EF."""
    w, d = 2, 1024
    g, e, gin, mask, gmask, ws, tau, p, valid = _level_inputs(w, d, 9)
    p = jnp.asarray([0.0, 1.0])
    valid = jnp.ones((w,))
    gout, e_new, nnz, _ = ops.cl_fuse_level(g, e, gin, ws, tau, p, valid,
                                            mode="always")
    np.testing.assert_array_equal(np.asarray(gout[0]), np.asarray(gin[0]))
    np.testing.assert_allclose(np.asarray(e_new[0]),
                               np.asarray(ws[0] * g[0] + e[0]), rtol=1e-6)
    assert int(nnz[0]) == int(jnp.sum(gin[0] != 0))
