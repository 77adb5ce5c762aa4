"""Aggregation rounds: ``jit(execute)`` of a compiled plan, round after round.

Every round is CL-SIA with the threshold sparsifier on the chain plan of
K clients, the case the reference covers. The traffic file gives K, the
τ search (``tau_impl``, branch and rounds), the Top-Q share, the number of
ring segments the model's flat gradient is cut into (one chip's share of
the deployment) and the size of the gradient pool. Gradients are drawn on the device from the seed, as
``pool`` sets of ``[K, d]``, and cycled; error feedback is carried from
round to round, as in training.

Correctness: a reservoir sample of ``check_rounds`` window rounds, drawn
from the seed, is compared after the window with ``bench/refs/sia.py``
run on the same inputs (the gradients and the carried error feedback the
round was given).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, model_sizes
from bench.refs import sia as ref
from repro.agg import compile_plan, execute
from repro.core.algorithms import AggConfig, AggKind


class Driver:
    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int,
                 devices, seconds: float = 0.0):
        self.traffic, self.limits = traffic, limits
        self.seed = seed
        self.k = int(traffic["clients"])
        self.d = model_sizes.flat_params(config) // int(traffic["segments"])
        self.q = max(1, int(traffic["q_frac"] * self.d))
        sp = traffic["sparsifier"]
        self.cfg = AggConfig(kind=AggKind.CL_SIA, q=self.q,
                             topq_impl="threshold",
                             tau_impl=sp.get("tau_impl", "scan"),
                             hist_branch=int(sp["hist_branch"]),
                             hist_rounds=int(sp["hist_rounds"]))
        self.n_pool = int(traffic["pool"])
        self.rng = np.random.default_rng(
            np.random.SeedSequence(int(seed) % (1 << 128)))
        self.samples: list = []
        self.rounds = 0

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        k, d = self.k, self.d
        n = self.n_pool

        @jax.jit
        def draw(key):
            keys = jax.random.split(key, n)
            return tuple(jax.random.normal(kk, (k, d), jnp.float32)
                         for kk in keys)

        self.pool = jax.block_until_ready(draw(data.seed_key(self.seed)))
        self.w = jax.device_put(jnp.full((k,), 1.0 / k, jnp.float32))
        self.part = jax.device_put(jnp.ones((k,), jnp.float32))
        self.plan = jax.device_put(compile_plan(k))
        self.e = jnp.zeros((k, d), jnp.float32)
        self.fn = jax.jit(functools.partial(execute, self.cfg))
        # two warm rounds: the first compiles (or loads) the program
        for _ in range(2):
            self._round()
        self.rounds = 0

    def _round(self):
        g = self.pool[self.rounds % self.n_pool]
        e_in = self.e
        res = self.fn(self.plan, g, e_in, self.w, participate=self.part)
        jax.block_until_ready(res)
        self.e = res.e_new
        return g, e_in, res

    # -- the window -----------------------------------------------------------
    def step(self) -> int:
        idx = self.rounds
        g, e_in, res = self._round()
        self.rounds += 1
        # reservoir sample of the window's rounds (Algorithm R), seeded
        keep = int(self.traffic["check_rounds"])
        if len(self.samples) < keep:
            self.samples.append((idx, g, e_in, res))
        else:
            j = int(self.rng.integers(0, idx + 1))
            if j < keep:
                self.samples[j] = (idx, g, e_in, res)
        return 1

    def shape(self) -> dict:
        return {"unit": "round", "clients": self.k, "hops": self.k,
                "d": self.d, "entry_bytes": 4}

    # -- correctness ----------------------------------------------------------
    def check(self, variants=()):
        """→ (checks [(name, value, limit)], attempted, failed, readings).

        ``readings["program"]`` holds the numbers read off the program and,
        for each of ``variants`` ("control": the reference in bfloat16 put
        in the program's place), the same numbers read off that stand-in."""
        samples = self.samples
        del self.pool, self.e, self.fn
        self.samples = []
        names = ("agg_err", "ef_err", "nnz_err", "bits_err")
        worst = {v: dict.fromkeys(names, 0.0) for v in (None,) + variants}
        failed = 0
        for _, g, e_in, res in samples:
            want = self._reference(g, e_in, jnp.float32)
            got = gaps(res.aggregate, res.e_new, res.stats.nnz_out,
                       res.stats.bits, *want)
            if any(v > self.limits[n] for n, v in got.items()):
                failed += 1
            per = {None: got}
            if "control" in variants:
                agg, e_new, nnz, bits = self._reference(g, e_in,
                                                        jnp.bfloat16)
                per["control"] = gaps(agg, e_new, nnz, bits, *want)
            for v, vals in per.items():
                for n, x in vals.items():
                    worst[v][n] = max(worst[v][n], x)
        checks = [(n, worst[None][n], self.limits[n]) for n in names]
        readings = {v: worst[v] for v in variants}
        readings["program"] = worst[None]
        return checks, self.rounds, failed, readings

    def _reference(self, g, e_in, dtype):
        w_h = np.asarray(jax.device_get(self.w))
        p_h = np.asarray(jax.device_get(self.part))
        return ref.cl_sia_chain(
            lambda k: (g[k], e_in[k]), w_h, p_h, q=self.q,
            branch=self.cfg.hist_branch, rounds=self.cfg.hist_rounds,
            dtype=dtype)


def gaps(agg, e_new, nnz, bits, agg_ref, e_ref, nnz_ref, bits_ref) -> dict:
    """The numbers that decide ``correct`` for one round: the largest gap
    of the aggregate and of each client's error feedback, relative to the
    reference's largest magnitude, and the relative gap of the §V
    counters."""
    def rel_max(a, b):
        scale = float(jnp.max(jnp.abs(b)))
        return float(jnp.max(jnp.abs(a - b))) / max(scale, 1e-30)

    nnz = np.asarray(jax.device_get(nnz), np.float64)
    bits = np.asarray(jax.device_get(bits), np.float64)
    return {
        "agg_err": rel_max(agg, agg_ref),
        "ef_err": max(rel_max(e_new[k], e_ref[k])
                      for k in range(len(e_ref))),
        "nnz_err": float(np.max(np.abs(nnz - nnz_ref)
                                / np.maximum(nnz_ref, 1))),
        "bits_err": float(abs(bits.sum() - bits_ref.sum())
                          / max(bits_ref.sum(), 1.0)),
    }
