"""Federated train steps: ``jit(build_train_step(...))`` on one client chip.

One client on a 1x1 mesh runs the rotated ring's one hop: CL-SIA with
exact Top-Q, G and EF in bfloat16, the case the reference covers. The
configuration file gives the model's sizes; the traffic file the Top-Q
share, the optimizer, the batch and sequence length. Set-up draws the
weights (one jitted call, on the device, in the configuration's dtypes)
and a pool of token batches from the seed, builds the train state, and
drives the compiled step through its first three steps, reading what the
reference needs from them; the window goes on with the same step and
state.

Correctness (``bench/refs/mamba2.py``): the norm of the first aggregate as
the optimizer gets it (from AdamW's first moment after step 1) and of the
master's change after step 3, over all leaves; the error feedback after
step 1; the §V bits of each of the first three steps. The same two norms
by their worst leaf, and each step's loss, are read too; the limits file
names the numbers compared.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, model_sizes
from bench.compare import norm_gap
from bench.refs import mamba2 as ref
from repro.agg.device import ring_chain_plan
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.launch.mesh import make_mesh
from repro.models.model import param_specs
from repro.optim.optimizers import FlatOptState, OptConfig
from repro.train.state import TrainConfig, TrainState
from repro.train.step import build_train_step, state_shardings

CHECK_STEPS = 3


def model_config(c: dict) -> ModelConfig:
    """The program's config object for the sizes the file states."""
    return ModelConfig(
        name=c["name"], family="ssm", num_layers=int(c["n_layer"]),
        d_model=int(c["d_model"]), num_heads=0, num_kv_heads=0, d_ff=0,
        vocab_size=int(c["vocab_size"]),
        tie_embeddings=bool(c["tie_embeddings"]),
        ssm_state=int(c["d_state"]), ssm_headdim=int(c["headdim"]),
        ssm_expand=int(c["expand"]), ssm_chunk=int(c["chunk_size"]),
        ssm_conv=int(c["d_conv"]), norm_eps=float(c["norm_eps"]),
        param_dtype=c["param_dtype"], source=c["source"])


def init_params(key, c: dict):
    """Mamba2 weights as the published initialisation draws them: linear
    layers N(0, 1/fan_in) (the output projection rescaled by 1/sqrt(2L)),
    embedding N(0, 0.02²), conv U(±1/sqrt(k)), A = -U(1, 16), dt from a
    log-uniform [0.001, 0.1] through the inverse softplus, D = 1, norms 1."""
    m = model_sizes.mamba2_dims(c)
    dt_ = jnp.dtype(c["param_dtype"])
    L, d, di, h = m["layers"], m["d"], m["di"], m["h"]
    ks = jax.random.split(key, 6)

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt_)

    dt0 = jnp.exp(jax.random.uniform(ks[4], (L, h), jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal(ks[0], (m["vocab"], d), 0.02),
        "final_norm": jnp.ones((d,), dt_),
        "layers": {
            "ln": jnp.ones((L, d), dt_),
            "mamba": {
                "a_log": jnp.log(jax.random.uniform(ks[5], (L, h),
                                                    jnp.float32, 1.0, 16.0)),
                "conv_w": (jax.random.uniform(
                    ks[1], (L, m["conv"], m["conv_ch"]), jnp.float32,
                    -1.0, 1.0) / math.sqrt(m["conv"])).astype(dt_),
                "d_skip": jnp.ones((L, h), jnp.float32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "in_proj": normal(ks[2], (L, d, m["in_proj"]),
                                  1 / math.sqrt(d)),
                "norm": jnp.ones((L, di), dt_),
                "out_proj": normal(ks[3], (L, di, d),
                                   1 / math.sqrt(di * 2 * L)),
            },
        },
    }


def zipf_tokens(key, shape, vocab: int, s: float):
    """Token ids with Zipf(s) frequencies over a shuffled vocabulary."""
    kp, ku = jax.random.split(key)
    p = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -s
    cdf = jnp.cumsum(p) / jnp.sum(p)
    rank = jnp.searchsorted(cdf, jax.random.uniform(ku, shape))
    perm = jax.random.permutation(kp, vocab)
    return perm[jnp.minimum(rank, vocab - 1)].astype(jnp.int32)


class Driver:
    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int,
                 devices, seconds: float = 0.0):
        self.c, self.traffic, self.limits = config, traffic, limits
        self.seed = seed
        self.cfg = model_config(config)
        o = traffic["optimizer"]
        self.opt = OptConfig(name=o["name"], lr=float(o["lr"]),
                             b1=float(o["b1"]), b2=float(o["b2"]),
                             eps=float(o["eps"]),
                             weight_decay=float(o["weight_decay"]))
        self.tc = TrainConfig(
            agg=AggConfig(kind=AggKind.CL_SIA, q=1, topq_impl="exact"),
            opt=self.opt, q_frac=float(traffic["q_frac"]),
            agg_dtype="bfloat16", ef_dtype="bfloat16",
            lr_warmup=int(o["warmup"]), lr_decay_steps=int(o["decay_steps"]))
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.q = max(1, int(self.tc.q_frac
                            * model_sizes.flat_params(config)))
        self.steps = 0
        self.losses: list = []
        self.bits: list = []

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        c = self.c
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.plan = ring_chain_plan(1)
        key = data.seed_key(self.seed)
        k_w, k_b = jax.random.split(key)
        with jax.set_mesh(self.mesh):
            shard = state_shardings(self.cfg, self.tc, self.mesh,
                                    topology=self.plan)
            efd = jnp.dtype(self.tc.ef_dtype)

            def make_state(k):
                params = init_params(k, c)
                master = ref.flatten(params)
                return TrainState(
                    step=jnp.int32(0), params=params, master=master,
                    opt=FlatOptState(jnp.int32(0), jnp.zeros_like(master),
                                     jnp.zeros_like(master)),
                    ef=jnp.zeros((1, master.shape[0]), efd), tcs_prev=None,
                    stage_ef=None)

            want = jax.tree.map(lambda s: (s.shape, s.dtype),
                                param_specs(self.cfg))
            got = jax.eval_shape(lambda k: init_params(k, c), k_w)
            if jax.tree.map(lambda s: (s.shape, s.dtype), got) != want:
                raise ValueError("the configuration's weights do not match "
                                 "the program's parameter tree")
            self.state = jax.jit(make_state, out_shardings=shard)(k_w)
            n_pool = int(self.traffic["batch_pool"])
            v = int(c["vocab_size"])

            @jax.jit
            def draw(k):
                t = zipf_tokens(k, (n_pool, self.batch, self.seq + 1), v,
                                float(self.traffic["zipf_s"]))
                return t[:, :, :-1], t[:, :, 1:]

            self.tokens, self.labels = draw(k_b)
            self.fn = jax.jit(build_train_step(self.cfg, self.tc, self.mesh,
                                               topology=self.plan))
            self.params0 = self.state.params
            slices = ref.leaf_slices(self.state.params)
            master0 = self.state.master
            for i in range(CHECK_STEPS):
                self._step()
                if i == 0:
                    # AdamW's first moment after one step is (1 - b1)·g
                    first = self.state.opt.m / (1.0 - self.opt.b1)
                    self.first_norms = _leaf_norms(first, slices)
                    self.ef1 = self.state.ef[0]
            self.change_norms = _leaf_norms(self.state.master - master0,
                                            slices)
            jax.block_until_ready(self.state)

    def _batch(self, i: int) -> dict:
        j = i % self.tokens.shape[0]
        return {"tokens": self.tokens[j], "labels": self.labels[j]}

    def _step(self) -> None:
        with jax.set_mesh(self.mesh):
            self.state, metrics = self.fn(self.state, self._batch(self.steps))
        jax.block_until_ready(self.state)
        self.losses.append(metrics["loss"])
        self.bits.append(metrics["agg_bits"])
        self.steps += 1

    # -- the window -----------------------------------------------------------
    def step(self) -> int:
        self._step()
        return 1

    def shape(self) -> dict:
        return {"unit": "step", "tokens": self.batch * self.seq,
                "config": self.c}

    # -- correctness ----------------------------------------------------------
    def check(self, variants=()):
        """→ (checks, attempted, failed, readings). ``readings["program"]``
        holds every number read off the program; each variant the same
        numbers read off a stand-in: "control" is the reference with float8
        (e4m3) matrix inputs, "half_batch" the reference fed half of each
        batch's rows; "leaves" the per-leaf norms (program, reference) of
        the first update and of the change."""
        losses = [float(x) for x in self.losses[:CHECK_STEPS]]
        bits = [float(x) for x in self.bits[:CHECK_STEPS]]
        params0 = self.params0
        batches = [(self.tokens[i], self.labels[i])
                   for i in range(CHECK_STEPS)]
        del self.state, self.fn
        slices = ref.leaf_slices(params0)
        want = reference(self.c, self.traffic, params0, batches, self.q)
        first = _leaf_norms(want["first"], slices)
        change = _leaf_norms(want["change"], slices)
        got = gaps(losses, self.first_norms, self.change_norms, want,
                   first, change)
        got["ef1_err"] = diff_norm(self.ef1, want["ef1"])
        got["bits_err"] = bits_gap(bits, want["bits"])
        checks = [(n, v, self.limits[n]) for n, v in got.items()
                  if n in self.limits]
        failed = int(any(v > lim for _, v, lim in checks))
        readings = {"program": got}
        if variants:
            readings["leaves"] = {
                n: [self.first_norms[n], first[n], self.change_norms[n],
                    change[n]] for n in first}
        for v in variants:
            if v == "control":
                alt = reference(self.c, self.traffic, params0, batches,
                                self.q, mm_dtype=jnp.float8_e4m3fn)
            elif v == "half_batch":
                half = [(t[: t.shape[0] // 2], lab[: lab.shape[0] // 2])
                        for t, lab in batches]
                alt = reference(self.c, self.traffic, params0, half, self.q)
            else:
                raise ValueError(f"unknown variant {v!r}")
            readings[v] = gaps(alt["loss"],
                               _leaf_norms(alt["first"], slices),
                               _leaf_norms(alt["change"], slices), want,
                               first, change)
            readings[v]["ef1_err"] = diff_norm(alt["ef1"], want["ef1"])
            readings[v]["bits_err"] = bits_gap(alt["bits"], want["bits"])
        return checks, self.steps, failed, readings


def diff_norm(a, b) -> float:
    """‖a − b‖ / ‖b‖ of two flat vectors, in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def bits_gap(bits, want) -> float:
    """The largest gap of a step's §V bits, exact counters; the program
    keeps them in float32, so the reference's count is rounded to it."""
    return float(max(abs(a - float(np.float32(b)))
                     for a, b in zip(bits, want)))


def gaps(losses, first_norms, change_norms, want, first, change):
    def whole(norms):
        return float(np.sqrt(sum(v * v for v in norms.values())))

    def rel(a, b):
        return abs(a - b) / b

    return {
        "first_update_err": rel(whole(first_norms), whole(first)),
        "change3_err": rel(whole(change_norms), whole(change)),
        "first_update_leaf_err": norm_gap(first_norms, first),
        "change3_leaf_err": norm_gap(change_norms, change),
        "loss_err": max(rel(a, b) for a, b in zip(losses, want["loss"])),
    }


def _leaf_norms(flat, slices: dict) -> dict:
    return {n: float(jnp.linalg.norm(flat[s])) for n, s in slices.items()}


def reference(c: dict, traffic: dict, params0, batches, q: int,
              mm_dtype=None) -> dict:
    m = model_sizes.mamba2_dims(c)
    dims = {k: m[k] for k in ("di", "n", "h", "p")}
    o = traffic["optimizer"]
    return ref.train(params0, batches, steps=len(batches), dims=dims,
                     vocab=int(c["vocab_size"]), eps=float(c["norm_eps"]),
                     q=q, opt=o, mm_dtype=mm_dtype)
