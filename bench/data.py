"""Inputs the benchmark draws from its seed, on the device, in one call each.

``mnist_like``: a 10-class, 784-feature data set shaped like MNIST: smooth
class templates (7x7 random images resized to 28x28, scaled to norm 6),
per-sample Gaussian noise and a random intensity scale per sample. Its
templates are fixed by the seed too, so every seed gives a different data
set of the same sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A threefry key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed) % (1 << 128)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


@functools.partial(jax.jit, static_argnames=("clients", "per_client",
                                             "dim", "classes"))
def mnist_like(key, *, clients: int, per_client: int, dim: int = 784,
               classes: int = 10):
    """→ (x [clients, per_client, dim] f32, y [clients, per_client] i32)."""
    kt, kn, ky, kx, ks = jax.random.split(key, 5)
    side = int(round(dim ** 0.5))
    coarse = jax.random.normal(kt, (classes, 7, 7))
    t = jax.image.resize(coarse, (classes, side, side), "bilinear")
    t = t.reshape(classes, dim)
    t = t / jnp.linalg.norm(t, axis=1, keepdims=True) * 6.0
    t = t + 0.1 * jax.random.normal(kn, (classes, dim))
    n = clients * per_client
    y = jax.random.randint(ky, (n,), 0, classes)
    x = t[y] + jax.random.normal(kx, (n, dim))
    x = x * (0.7 + 0.6 * jax.random.uniform(ks, (n, 1)))
    return (x.reshape(clients, per_client, dim).astype(jnp.float32),
            y.reshape(clients, per_client).astype(jnp.int32))
