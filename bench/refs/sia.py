"""Plain reference of one sparse incremental-aggregation round on a chain.

Written from the paper (arXiv:2407.18200, Algorithms 3 and 5) and the
stated semantics of the two Top-Q sparsifiers; it imports nothing of the
program. Client K-1 is the far end of the chain and client 0 is next to
the parameter server: partial aggregates flow K-1 → … → 0 → PS.

Sparsifiers:

* ``exact``: keep the q entries of largest magnitude (ties by lowest index);
* ``threshold``: keep ``|x| >= tau``, where tau is found by ``rounds`` rounds
  of branch-and-bisect on the bracket ``[0, max|x| * (1 + 1e-6)]`` with
  ``branch`` equal steps, each round moving the bracket to the highest
  candidate that still keeps at least q entries.

Every hop's arithmetic runs in ``dtype``: float32 is the reference, and
bfloat16 the lower-precision control that a correct run must not match.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def index_bits(d: int) -> int:
    return max(1, math.ceil(math.log2(d)))


def _kth_largest(mag, q: int):
    """The q-th largest entry of ``mag`` (a 1-D vector)."""
    return jnp.sort(mag)[mag.shape[0] - q]


def threshold_tau(mag, q: int, branch: int, rounds: int):
    """tau of the branch-and-bisect threshold sparsifier."""
    dt = mag.dtype
    kth = _kth_largest(mag, q)
    hi = jnp.maximum(jnp.max(mag), jnp.asarray(1e-30, dt)) * jnp.asarray(
        1 + 1e-6, dt)
    lo = jnp.zeros((), dt)
    steps = jnp.arange(1, branch + 1, dtype=dt)
    for _ in range(rounds):
        w = (hi - lo) / branch
        taus = lo + w * steps
        jstar = jnp.sum((kth >= taus).astype(jnp.int32))
        lo = lo + jstar.astype(dt) * w
        hi = lo + w
    return jnp.maximum(lo, jnp.asarray(1e-30, dt))


def exact_keep(x, q: int):
    """Mask of the q largest |x| (ties by lowest index)."""
    idx = jnp.argsort(-jnp.abs(x), stable=True)[:q]
    return jnp.zeros(x.shape, bool).at[idx].set(True)


@functools.partial(jax.jit, static_argnames=("q", "branch", "rounds",
                                             "dtype"))
def cl_sia_hop(g, e, gamma, w, p, *, q: int, branch: int, rounds: int,
               dtype=jnp.float32):
    """Algorithm 3 (CL-SIA) at one client with the threshold sparsifier.

    → (gamma_out, e_new, nnz_out). A straggler (p = 0) forwards gamma
    unchanged and banks its whole weighted gradient."""
    g, e, gamma = (a.astype(dtype) for a in (g, e, gamma))
    w, p = jnp.asarray(w, dtype), jnp.asarray(p, dtype)
    gt = w * g + e
    gtil = p * gt + gamma
    mag = jnp.abs(gtil)
    tau = threshold_tau(mag, q, branch, rounds)
    keep = mag >= tau
    out = jnp.where(keep, gtil, 0)
    e_new = jnp.where(keep, 0, gtil)
    out = jnp.where(p > 0, out, gamma)
    e_new = jnp.where(p > 0, e_new, gt)
    return (out.astype(jnp.float32), e_new.astype(jnp.float32),
            jnp.sum(out != 0).astype(jnp.int32))


def cl_sia_chain(rows, weights, participate, *, q: int, branch: int,
                 rounds: int, dtype=jnp.float32):
    """One CL-SIA round over a chain, one client at a time.

    ``rows(k)`` → ``(g_k, e_k)`` on the device, so that only one client's
    rows need be resident beside the running aggregate.
    → (aggregate [d], e_new list of K [d], nnz [K], bits [K] (numpy))."""
    k_clients = len(weights)
    gamma = None
    e_new = [None] * k_clients
    nnz = np.zeros(k_clients, np.int64)
    for k in reversed(range(k_clients)):
        g, e = rows(k)
        if gamma is None:
            gamma = jnp.zeros(g.shape, jnp.float32)
        gamma, e_new[k], n = cl_sia_hop(
            g, e, gamma, float(weights[k]), float(participate[k]), q=q,
            branch=branch, rounds=rounds, dtype=dtype)
        nnz[k] = int(n)
    d = int(gamma.shape[0])
    bits = nnz.astype(np.float64) * (32 + index_bits(d))
    return gamma, e_new, nnz, bits
