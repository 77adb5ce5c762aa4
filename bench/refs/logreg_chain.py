"""Plain reference of the paper's §VI experiment: federated logistic
regression over a K-client chain with CL-TC-SIA (Algorithm 5), exact Top-Q.

Written from the paper (arXiv:2407.18200) in float64 NumPy; it imports
nothing of the program. Per round t:

1. each client k draws ``batch`` sample indices (uniform, with
   replacement) with ``jax.random`` keys split from the round key, and takes
   one SGD step from the global model: ``g_k = -lr * grad_k``;
2. the global mask ``m = s(w^t - w^{t-1}, Q_G)`` (empty in the first round,
   where the model has not moved);
3. from the far end (client K-1) to the PS, each client forwards
   ``Γ + S(Λ̃, Q_L)`` with ``Γ = m·(γ_in + g̃_k)``, ``Λ̃ = (1-m)·(γ_in + g̃_k)``
   and keeps ``Λ̃ - S(Λ̃, Q_L)`` as error feedback, ``g̃_k = g_k + e_k``;
4. the PS applies ``w += γ / K`` and logs the mean cross-entropy over all
   training samples.

Bits per hop follow §V: ω per masked word, ω + ⌈log₂ d⌉ per local index.
Ties in Top-Q go to the lowest index. With ``bf16=True`` every array the
round computes is rounded to bfloat16: the lower-precision control of the
configuration's float32.
"""

from __future__ import annotations

import math

import jax
import ml_dtypes
import numpy as np


def topq_mask(x, q: int):
    idx = np.argsort(-np.abs(x), kind="stable")[:q]
    m = np.zeros(x.shape, x.dtype)
    m[idx] = 1
    return m


def mean_ce(w, b, x, y):
    z = x @ w + b
    z = z - z.max(axis=-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
    return -np.mean(logp[np.arange(len(y)), y])


def ce_grad(w, b, x, y):
    z = x @ w + b
    z = z - z.max(axis=-1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=-1, keepdims=True)
    p[np.arange(len(y)), y] -= 1
    p /= len(y)
    return x.T @ p, p.sum(axis=0)


def minibatch_indices(seed: int, rounds: int, clients: int, batch: int,
                      per_client: int) -> list:
    """The sampling stream: the round key splits off the running key, K
    client keys split from it, each drawing ``batch`` indices."""
    rng = jax.random.PRNGKey(seed)
    out = []
    for _ in range(rounds):
        rng, kb = jax.random.split(rng)
        keys = jax.random.split(kb, clients)
        idx = jax.vmap(lambda kk: jax.random.randint(
            kk, (batch,), 0, per_client))(keys)
        out.append(np.asarray(idx))
    return out


def _bf16(a):
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def run(x, y, *, seed: int, rounds: int, batch: int, lr: float,
        q_local: int, q_global: int, classes: int = 10, omega: int = 32,
        bf16: bool = False) -> dict:
    """→ {"loss": [rounds], "bits": [rounds], "w": [rounds + 1] flat models
    (w, then b)}."""
    dtype = np.float32 if bf16 else np.float64
    rnd = _bf16 if bf16 else (lambda a: a)
    x = rnd(np.asarray(x, dtype))
    y = np.asarray(y)
    k, n, dim = x.shape
    wd = dim * classes
    d = wd + classes
    ib = max(1, math.ceil(math.log2(d)))
    all_x, all_y = x.reshape(-1, dim), y.reshape(-1)
    w = np.zeros(d, dtype)
    w_prev = w.copy()
    ef = np.zeros((k, d), dtype)
    losses, bits, models = [], [], [w.copy()]
    for idx in minibatch_indices(seed, rounds, k, batch, n):
        W, b = w[:wd].reshape(dim, classes), w[wd:]
        delta = w - w_prev
        m = (topq_mask(delta, q_global) if np.any(delta != 0)
             else np.zeros(d, dtype))
        w_prev = w.copy()
        gamma = np.zeros(d, dtype)
        round_bits = 0.0
        for c in reversed(range(k)):
            gw, gb = ce_grad(W, b, x[c, idx[c]], y[c, idx[c]])
            g = rnd(-lr * np.concatenate([gw.reshape(-1), gb]).astype(dtype))
            gt = rnd(g + ef[c])
            big = rnd(m * (gamma + gt))
            lam_t = rnd((1 - m) * (gamma + gt))
            lam = lam_t * topq_mask(lam_t, q_local)
            ef[c] = lam_t - lam
            gamma = rnd(big + lam)
            nz_local = int(np.count_nonzero(gamma * (1 - m)))
            nz_global = int(np.count_nonzero(m))
            round_bits += omega * nz_global + (omega + ib) * nz_local
        w = rnd(w + gamma / k)
        losses.append(float(mean_ce(w[:wd].reshape(dim, classes), w[wd:],
                                    all_x, all_y)))
        bits.append(round_bits)
        models.append(w.copy())
    return {"loss": losses, "bits": bits, "w": models}
