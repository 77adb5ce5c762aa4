"""Deterministic synthetic datasets (the container has no downloads).

* ``make_synthetic_mnist`` — a 10-class, 784-dim image-like dataset with
  MNIST's exact dimensionality so the paper's d=7850 logistic-regression
  setup is reproduced bit-for-bit in structure. Classes are smooth random
  templates + per-sample noise + random shifts; linear separability is
  partial (top-1 linear accuracy plateaus ≈ 90–97%), giving convergence
  curves with the same qualitative shape as MNIST's.
* ``BigramLM`` — a random (but fixed) bigram language: sequences carry
  real mutual information, so LM training losses measurably decrease —
  unlike uniform random tokens.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array


class Dataset(NamedTuple):
    x: Array      # [N, 784] float32
    y: Array      # [N] int32


def _templates(key, num_classes: int = 10, dim: int = 784) -> Array:
    """Smooth class templates: low-frequency random images, unit-ish norm."""
    side = int(dim ** 0.5)
    k1, k2 = jax.random.split(key)
    coarse = jax.random.normal(k1, (num_classes, 7, 7))
    up = jax.image.resize(coarse, (num_classes, side, side), "bilinear")
    t = up.reshape(num_classes, dim)
    t = t / jnp.linalg.norm(t, axis=1, keepdims=True) * 6.0
    return t + 0.1 * jax.random.normal(k2, (num_classes, dim))


def make_synthetic_mnist(key, n: int, *, num_classes: int = 10,
                         dim: int = 784, noise: float = 1.0,
                         template_seed: int = 42) -> Dataset:
    """``key`` draws the samples; the class templates are dataset-level
    constants fixed by ``template_seed`` (train/test must share them)."""
    ky, kn, ks = jax.random.split(key, 3)
    t = _templates(jax.random.PRNGKey(template_seed), num_classes, dim)
    y = jax.random.randint(ky, (n,), 0, num_classes)
    x = t[y] + noise * jax.random.normal(kn, (n, dim))
    # per-sample random intensity scaling (mimics stroke-thickness variance)
    scale = 0.7 + 0.6 * jax.random.uniform(ks, (n, 1))
    x = x * scale
    return Dataset(x=x.astype(jnp.float32), y=y.astype(jnp.int32))


class BigramLM(NamedTuple):
    key: Array            # row t of the transition logits is drawn from
    vocab: int            # fold_in(key, t): the [V, V] table is never held
    concentration: float


def make_bigram_lm(key, vocab: int, *, concentration: float = 3.0
                   ) -> BigramLM:
    """Random sparse-ish bigram language (fixed by seed).

    The [V, V] transition logits are drawn row by row as tokens need them:
    at a published vocabulary (50,280) the whole table would take 10 GB."""
    return BigramLM(key=key, vocab=vocab, concentration=concentration)


def transition_logits(lm: BigramLM, tok: Array) -> Array:
    """Logit rows [..., V] of the current tokens ``tok`` [...]."""
    row = lambda t: jax.random.normal(jax.random.fold_in(lm.key, t),
                                      (lm.vocab,))
    flat = jax.vmap(row)(tok.reshape(-1)) * lm.concentration
    return flat.reshape(tok.shape + (lm.vocab,))


def sample_bigram(lm: BigramLM, key, batch: int, seq: int) -> Array:
    """Sample token sequences [B, S+1] from the bigram chain."""
    k0, kseq = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, lm.vocab)

    def step(tok, k):
        nxt = jax.random.categorical(k, transition_logits(lm, tok), axis=-1)
        return nxt, nxt

    keys = jax.random.split(kseq, seq)
    _, toks = jax.lax.scan(step, first, keys)
    out = jnp.concatenate([first[None], toks], axis=0)      # [S+1, B]
    return jnp.moveaxis(out, 0, 1).astype(jnp.int32)         # [B, S+1]


def lm_batch(lm: BigramLM, key, batch: int, seq: int) -> dict:
    toks = sample_bigram(lm, key, batch, seq)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
