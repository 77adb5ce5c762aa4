"""Unit + property tests for Top-Q primitives."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import sparsify as sp


def test_topq_keeps_largest():
    x = jnp.asarray([1.0, -5.0, 0.5, 3.0, -2.0])
    out = sp.topq(x, 2)
    np.testing.assert_allclose(np.asarray(out), [0, -5, 0, 3, 0])


def test_topq_mask_matches_topq():
    x = jax.random.normal(jax.random.PRNGKey(0), (257,))
    for q in (1, 17, 256, 257, 300):
        np.testing.assert_allclose(
            np.asarray(sp.topq(x, q)),
            np.asarray(sp.topq_mask(x, q) * x))


def test_topq_edge_cases():
    x = jnp.asarray([1.0, 2.0, 3.0])
    assert int(sp.nnz(sp.topq(x, 0))) == 0
    np.testing.assert_allclose(np.asarray(sp.topq(x, 5)), np.asarray(x))


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_topq_property_count_and_energy(q, seed):
    """‖S(x,Q)‖₀ = min(Q, d) and S keeps maximal energy (optimality, eq. 3)."""
    d = 256
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    out = sp.topq(x, q)
    assert int(sp.nnz(out)) == min(q, d)
    # energy of kept = sum of q largest squares
    kept = np.sort(np.abs(np.asarray(out)))[::-1][:q]
    best = np.sort(np.abs(np.asarray(x)))[::-1][:q]
    np.testing.assert_allclose(np.sort(kept), np.sort(best), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 500), st.integers(0, 2**31 - 1))
def test_threshold_topq_overselects_boundedly(q, seed):
    d = 4096
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    tau = sp.threshold_for_topq(x, q, branch=64, rounds=3)
    kept = int(jnp.sum(jnp.abs(x) >= tau))
    assert kept >= min(q, d)
    # over-selection bounded by within-bin ties: loose 2% + 2 bound
    assert kept <= min(q, d) + max(2, int(0.02 * d))


def test_threshold_matches_exact_on_distinct_values():
    x = jnp.asarray(np.random.default_rng(0).permutation(1000).astype(
        np.float32)) + 1.0
    tau = sp.threshold_for_topq(x, 100, branch=64, rounds=4)
    kept = int(jnp.sum(jnp.abs(x) >= tau))
    assert kept == 100


def test_compact_scatter_roundtrip():
    key = jax.random.PRNGKey(3)
    d, q = 512, 40
    x = sp.topq(jax.random.normal(key, (d,)), q)
    vals, idx, cnt = sp.compact(x, q)
    assert int(cnt) == q
    back = sp.scatter(vals, idx, d)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))


def test_compact_pads_with_sentinel():
    x = jnp.zeros((16,)).at[3].set(5.0)
    vals, idx, cnt = sp.compact(x, 4)
    assert int(cnt) == 1
    assert int((idx == 16).sum()) == 3          # sentinel = d
    np.testing.assert_allclose(np.asarray(sp.scatter(vals, idx, 16)),
                               np.asarray(x))


def test_mask_union_and_support():
    a = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    b = jnp.asarray([0.0, 1.0, 1.0, 0.0])
    np.testing.assert_allclose(np.asarray(sp.mask_union(a, b)), [1, 1, 1, 0])
    np.testing.assert_allclose(
        np.asarray(sp.support(jnp.asarray([0.0, -2.0, 3.0]))), [0, 1, 1])


# ---------------------------------------------------------------------------
# Exact Top-Q by the counting select: bitwise the lax.top_k support
# ---------------------------------------------------------------------------

def _top_k_keep(x, q):
    """The ``lax.top_k`` support of a vector: ties lowest index first."""
    _, idx = jax.lax.top_k(jnp.abs(x), q)
    return jnp.zeros(x.shape, bool).at[idx].set(True)


def _lanes(kind, d=300):
    """[3, d] float32 lanes of one kind, each with its own tie structure."""
    rng = np.random.default_rng(sum(map(ord, kind)))
    if kind == "integer_ties":
        lanes = [rng.integers(-3, 4, d), rng.integers(-1, 2, d),
                 np.repeat(rng.integers(-5, 6, d // 10 + 1), 10)[:d]]
    elif kind == "bf16_quantized":
        lanes = [np.asarray(jnp.asarray(rng.standard_normal(d) * s,
                                        jnp.bfloat16).astype(jnp.float32))
                 for s in (1.0, 1e-3, 3e4)]
    elif kind == "signed_zeros":
        z = np.where(rng.random(d) < 0.5, 0.0, -0.0)
        one = z.copy()
        one[rng.integers(0, d, 5)] = -2.0
        lanes = [z, -z, one]
    elif kind == "inf":
        a = rng.integers(-2, 3, d).astype(np.float64)
        a[rng.random(d) < 0.1] = np.inf
        a[rng.random(d) < 0.05] = -np.inf
        lanes = [a, np.full(d, -np.inf), np.where(a == 0, np.inf, a)]
    else:  # "distinct"
        lanes = [rng.standard_normal(d) * s for s in (1.0, 1e-30, 1e-42)]
    return jnp.asarray(np.stack(lanes), jnp.float32)


@pytest.mark.parametrize("q_of_d", [lambda d: 0, lambda d: 1,
                                    lambda d: d // 3, lambda d: d - 1,
                                    lambda d: d, lambda d: d + 1],
                         ids=["0", "1", "third", "d-1", "d", "d+1"])
@pytest.mark.parametrize("kind", ["integer_ties", "bf16_quantized",
                                  "signed_zeros", "inf", "distinct"])
def test_counting_select_is_the_top_k_support(kind, q_of_d, monkeypatch):
    """With the gate at 1 every vector takes the counting select; its mask
    and ``topq`` output equal the ``lax.top_k`` ones bitwise: per vector,
    on [W, d] lanes at once, and vmapped, all under jit."""
    monkeypatch.setattr(sp, "_SELECT_MIN_D", 1)
    x = _lanes(kind)
    d = x.shape[-1]
    q = q_of_d(d)
    if q <= 0:
        want = jnp.zeros(x.shape, bool)
    elif q >= d:
        want = jnp.ones(x.shape, bool)
    else:
        want = jax.vmap(_top_k_keep, (0, None))(x, q)
    want_mask = np.asarray(want.astype(x.dtype))
    want_vals = np.asarray(jnp.where(want, x, 0))

    before = collections.Counter(sp.EXACT_PATHS)
    # fresh functions, so no trace cached under another gate is reused
    mask = jax.jit(lambda v: sp.topq_mask(v, q))
    np.testing.assert_array_equal(np.asarray(mask(x)), want_mask)
    np.testing.assert_array_equal(
        np.stack([np.asarray(mask(row)) for row in x]), want_mask)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(jax.vmap(lambda r: sp.topq_mask(r, q)))(x)),
        want_mask)
    vals = np.asarray(jax.jit(lambda v: sp.topq(v, q))(x))
    assert vals.tobytes() == want_vals.tobytes()      # -0.0 kept as -0.0
    if 0 < q < d:
        assert (want_mask.sum(axis=-1) == q).all()
        got = collections.Counter(sp.EXACT_PATHS)
        got.subtract(before)
        assert +got == collections.Counter({("select", 3, d, q): 2,
                                            ("select", 1, d, q): 2})


def test_exact_paths_record_the_gate():
    """The path counter records the counting select at and above the gate
    and ``lax.top_k`` below it, with (W, d, q), once per trace."""
    d = sp._SELECT_MIN_D
    before = collections.Counter(sp.EXACT_PATHS)
    for shape in [(d - 1,), (d,), (3, d), (1, d)]:
        jax.make_jaxpr(lambda v: sp.topq_mask(v, 7))(
            jax.ShapeDtypeStruct(shape, jnp.float32))
    jax.make_jaxpr(lambda v: sp.topq(v, 5))(
        jax.ShapeDtypeStruct((2, d - 1), jnp.float32))
    jax.make_jaxpr(lambda v: sp.topq_mask(v, 0))(        # keeps nothing
        jax.ShapeDtypeStruct((d,), jnp.float32))
    got = collections.Counter(sp.EXACT_PATHS)
    got.subtract(before)
    assert +got == collections.Counter({
        ("top_k", 1, d - 1, 7): 1, ("select", 1, d, 7): 2,
        ("select", 3, d, 7): 1, ("top_k", 2, d - 1, 5): 1})
