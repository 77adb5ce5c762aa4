"""Federated train steps of a hybrid Mamba2 / MoE / attention model
(Nemotron-H): ``jit(build_train_step(...))`` on one client chip, the state
donated.

One client on a 1x1 mesh runs the rotated ring's one hop: CL-SIA with
exact Top-Q, G and EF in bfloat16. The configuration file gives the model's
sizes in its source's keys (``hybrid_override_pattern``, the experts this
chip holds, ...); the traffic file the Top-Q share, the optimizer, the
batch and sequence length. Set-up draws the weights (one jitted call, on
the device) and a pool of token batches from the seed, builds the state,
and drives the compiled step through its first three steps, reading what
the reference needs from them; the window goes on with the same step and
state. The state is donated to each step (two would not fit the chip), so
the reference redraws the initial weights from the seed.

Correctness (``bench/refs/nemotron_h.py``): as ``train``'s cell, the norms
of the first aggregate (from AdamW's first moment after step 1) and of the
master's change after step 3, over all leaves and by their worst leaf; the
error feedback after step 1; each step's §V bits and loss; and each step's
``routed_slots``, the slots the expert layers routed to this chip's
experts. The limits file names the numbers compared.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench.drivers.train import CHECK_STEPS, bits_gap, gaps, zipf_tokens
from bench.refs import nemotron_h as ref
from bench.refs.mamba2 import flatten
from repro.agg.device import ring_chain_plan
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.launch.mesh import make_mesh
from repro.models.model import param_specs
from repro.optim.optimizers import FlatOptState, OptConfig
from repro.train.state import TrainConfig, TrainState
from repro.train.step import build_train_step, state_shardings

#: query rows of one block of the reference's quadratic SSD and attention
REF_BLOCK = 256


def sizes(c: dict) -> dict:
    """The sizes the configuration file states, under short names."""
    d = int(c["hidden_size"])
    h, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    g, n = int(c["n_groups"]), int(c["ssm_state_size"])
    di = h * p
    return {
        "pattern": c["hybrid_override_pattern"], "d": d, "di": di, "h": h,
        "p": p, "g": g, "n": n, "conv": int(c["conv_kernel"]),
        "conv_ch": di + 2 * g * n, "in_proj": 2 * di + 2 * g * n + h,
        "chunk": int(c["chunk_size"]),
        "hq": int(c["num_attention_heads"]),
        "hkv": int(c["num_key_value_heads"]), "dh": int(c["head_dim"]),
        "experts": int(c["n_routed_experts"]),
        "held": int(c["n_routed_experts_held"]),
        "first": int(c["experts_first"]),
        "topk": int(c["num_experts_per_tok"]),
        "f": int(c["moe_intermediate_size"]),
        "fs": (int(c["moe_shared_expert_intermediate_size"])
               * int(c["n_shared_experts"])),
        "scale": float(c["routed_scaling_factor"]),
        "vocab": int(c["vocab_size"]),
        "padded_vocab": int(c["assumed"]["padded_vocab"]),
        "eps": float(c["layer_norm_epsilon"]),
    }


def flat_params(c: dict) -> int:
    """Length of the flat gradient: every parameter this chip holds."""
    s = sizes(c)
    d = s["d"]
    per = {
        "M": (d * s["in_proj"] + s["conv"] * s["conv_ch"] + s["conv_ch"]
              + 3 * s["h"] + s["di"] + s["di"] * d),
        "E": d * s["experts"] + s["held"] * 2 * d * s["f"] + 2 * d * s["fs"],
        "*": 2 * d * (s["hq"] + s["hkv"]) * s["dh"],
    }
    return (sum(d + per[k] for k in s["pattern"])
            + 2 * s["padded_vocab"] * d + d)


def model_config(c: dict) -> ModelConfig:
    """The program's config object for the sizes the file states."""
    s = sizes(c)
    return ModelConfig(
        name=c["name"], family="hybrid_moe", num_layers=len(s["pattern"]),
        layer_pattern=s["pattern"], d_model=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], d_ff=s["f"],
        shared_d_ff=s["fs"], vocab_size=s["vocab"],
        tie_embeddings=bool(c["tie_word_embeddings"]), rope=False,
        num_experts=s["experts"], num_experts_per_tok=s["topk"],
        experts_held=s["held"], experts_first=s["first"],
        routed_scale=s["scale"], ssm_state=s["n"], ssm_headdim=s["p"],
        ssm_nheads=s["h"], ssm_groups=s["g"], ssm_chunk=s["chunk"],
        ssm_conv=s["conv"], ssm_conv_bias=bool(c["use_conv_bias"]),
        norm_eps=s["eps"], param_dtype=c["param_dtype"], source=c["source"])


def init_params(key, c: dict):
    """Weights as the file's ``assumed.init`` draws them: linear layers and
    the embedding N(0, 0.02²), the projections into the residual stream
    scaled by 1/sqrt(layers); conv weight and bias U(±1/sqrt(k)); A_log =
    log(1..H); dt log-uniform in [0.001, 0.1] through the inverse softplus;
    D = 1; norms 1."""
    s = sizes(c)
    dt_ = jnp.dtype(c["param_dtype"])
    d, pattern = s["d"], s["pattern"]
    keys = jax.random.split(key, len(pattern) + 2)
    out_std = 0.02 / math.sqrt(len(pattern))

    def normal(k, shape, std=0.02):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt_)

    def block(k, kind):
        ks = jax.random.split(k, 6)
        if kind == "M":
            dt0 = jnp.exp(jax.random.uniform(ks[3], (s["h"],), jnp.float32,
                                             math.log(1e-3), math.log(1e-1)))
            bound = 1.0 / math.sqrt(s["conv"])
            mixer = ("mamba", {
                "a_log": jnp.log(jnp.arange(1, s["h"] + 1,
                                            dtype=jnp.float32)),
                "conv_b": jax.random.uniform(
                    ks[1], (s["conv_ch"],), jnp.float32, -bound,
                    bound).astype(dt_),
                "conv_w": jax.random.uniform(
                    ks[2], (s["conv"], s["conv_ch"]), jnp.float32, -bound,
                    bound).astype(dt_),
                "d_skip": jnp.ones((s["h"],), jnp.float32),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "in_proj": normal(ks[0], (d, s["in_proj"])),
                "norm": jnp.ones((s["di"],), dt_),
                "out_proj": normal(ks[4], (s["di"], d), out_std),
            })
        elif kind == "E":
            mixer = ("moe", {
                "router": normal(ks[0], (d, s["experts"])),
                "shared_down": normal(ks[1], (s["fs"], d), out_std),
                "shared_up": normal(ks[2], (d, s["fs"])),
                "w_down": normal(ks[3], (s["held"], s["f"], d), out_std),
                "w_up": normal(ks[4], (s["held"], d, s["f"])),
            })
        else:
            mixer = ("attn", {
                "wk": normal(ks[0], (d, s["hkv"] * s["dh"])),
                "wo": normal(ks[1], (s["hq"] * s["dh"], d), out_std),
                "wq": normal(ks[2], (d, s["hq"] * s["dh"])),
                "wv": normal(ks[3], (d, s["hkv"] * s["dh"])),
            })
        return {mixer[0]: mixer[1], "ln": jnp.ones((d,), dt_)}

    return {
        "embed": normal(keys[0], (s["padded_vocab"], d)),
        "final_norm": jnp.ones((d,), dt_),
        "layers": [block(k, kind) for k, kind in zip(keys[2:], pattern)],
        "lm_head": normal(keys[1], (d, s["padded_vocab"])),
    }


def ref_dims(c: dict, seq: int) -> dict:
    """What ``bench/refs/nemotron_h.py`` reads of the sizes."""
    s = sizes(c)
    keep = ("pattern", "di", "h", "p", "g", "n", "hq", "hkv", "dh", "held",
            "first", "topk", "scale", "vocab", "eps")
    return dict({k: s[k] for k in keep}, block=min(REF_BLOCK, seq))


def _flat_norms(slices: dict):
    """jit: flat [d] → its norm over each leaf's slice, [leaves]."""
    return jax.jit(lambda flat: jnp.stack(
        [jnp.linalg.norm(flat[s]) for s in slices.values()]))


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
        # Beside the 8.45 GB state at 528 M entries, the Pallas node step's
        # operand blocks (six float32 copies of the vector) and a [1, D]
        # EF (twice its bytes in TPU tiles, read through a float32
        # relayout) do not fit the chip: the EF is flat and the node step
        # runs its jnp form, bit-exact to the kernels.
        self.tc = TrainConfig(
            agg=AggConfig(kind=AggKind.CL_SIA, q=1, topq_impl="exact",
                          kernel_mode="never"),
            opt=self.opt, q_frac=float(traffic["q_frac"]),
            agg_dtype="bfloat16", ef_dtype="bfloat16",
            lr_warmup=int(o["warmup"]), lr_decay_steps=int(o["decay_steps"]),
            ef_flat=True)
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.q = max(1, int(self.tc.q_frac * flat_params(config)))
        self.steps = 0
        self.losses: list = []
        self.bits: list = []
        self.counters: list = []

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        c = self.c
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.plan = ring_chain_plan(1)
        self.k_w, k_b = jax.random.split(data.seed_key(self.seed))
        # one compiled draw, so every redraw is bitwise the first
        self.draw = jax.jit(lambda k: init_params(k, c))
        with jax.set_mesh(self.mesh):
            shard = state_shardings(self.cfg, self.tc, self.mesh,
                                    topology=self.plan)
            efd = jnp.dtype(self.tc.ef_dtype)

            def make_state(params):
                # from the drawn bf16 weights as stored: inside one program
                # XLA may skip the round trip through bf16
                master = flatten(params)
                return TrainState(
                    step=jnp.int32(0), params=params, master=master,
                    opt=FlatOptState(jnp.int32(0), jnp.zeros_like(master),
                                     jnp.zeros_like(master)),
                    ef=jnp.zeros(master.shape, efd), tcs_prev=None,
                    stage_ef=None)

            want = jax.tree.map(lambda s: (s.shape, s.dtype),
                                param_specs(self.cfg))
            got = jax.eval_shape(lambda k: init_params(k, c), self.k_w)
            if jax.tree.map(lambda s: (s.shape, s.dtype), got) != want:
                raise ValueError("the configuration's weights do not match "
                                 "the program's parameter tree")
            self.slices = ref.leaf_slices(got)
            self.state = jax.jit(make_state, out_shardings=shard,
                                 donate_argnums=0)(self.draw(self.k_w))
            n_pool = int(self.traffic["batch_pool"])

            @jax.jit
            def draw(k):
                t = zipf_tokens(k, (n_pool, self.batch, self.seq + 1),
                                int(c["vocab_size"]),
                                float(self.traffic["zipf_s"]))
                return t[:, :, :-1], t[:, :, 1:]

            self.tokens, self.labels = draw(k_b)
            self.fn = jax.jit(build_train_step(self.cfg, self.tc, self.mesh,
                                               topology=self.plan),
                              donate_argnums=0)
            norms = _flat_norms(self.slices)
            for i in range(CHECK_STEPS):
                self._step()
                if i == 0:
                    # AdamW's first moment after one step is (1 - b1)·g
                    self.first_norms = self._named(
                        norms(self.state.opt.m) / (1.0 - self.opt.b1))
                    self.ef1 = np.asarray(self.state.ef)

            @jax.jit
            def change(master, params0):
                return jnp.stack([
                    jnp.linalg.norm(master[s] - jnp.ravel(l).astype(
                        jnp.float32))
                    for s, l in zip(self.slices.values(),
                                    jax.tree.leaves(params0))])

            self.change_norms = self._named(change(self.state.master,
                                                   self.draw(self.k_w)))

    def _named(self, norms) -> dict:
        return dict(zip(self.slices, (float(x) for x in np.asarray(norms))))

    def _batch(self, i: int) -> dict:
        j = i % self.tokens.shape[0]
        return {"tokens": self.tokens[j], "labels": self.labels[j]}

    def _step(self) -> None:
        with jax.set_mesh(self.mesh):
            self.state, metrics = self.fn(self.state, self._batch(self.steps))
        jax.block_until_ready(self.state)
        self.losses.append(metrics["loss"])
        self.bits.append(metrics["agg_bits"])
        self.counters.append((metrics["routed_slots"],
                              metrics["expert_rows"]))
        self.steps += 1

    def program_text(self) -> str:
        """The optimized HLO text of the timed step."""
        with jax.set_mesh(self.mesh):
            return self.fn.lower(self.state,
                                 self._batch(0)).compile().as_text()

    # -- the window -----------------------------------------------------------
    def step(self) -> int:
        self._step()
        return 1

    def shape(self) -> dict:
        """Sizes of a step, and each window step's counters: the slots
        routed to this chip's experts and the rows its expert matmuls ran
        (padding included)."""
        window = self.counters[CHECK_STEPS:]
        return {"unit": "step", "tokens": self.batch * self.seq,
                "batch": self.batch, "seq": self.seq, "config": self.c,
                "routed_slots": [int(r) for r, _ in window],
                "expert_rows": [int(e) for _, e in window]}

    # -- correctness ----------------------------------------------------------
    def check(self, variants=()):
        """→ (checks, attempted, failed, readings), as ``train``'s driver;
        the stand-ins are "control", the reference with float8 (e4m3)
        matrix inputs, and "half_batch", the reference fed half of each
        batch's rows."""
        losses = [float(x) for x in self.losses[:CHECK_STEPS]]
        bits = [float(x) for x in self.bits[:CHECK_STEPS]]
        slots = [int(r) for r, _ in self.counters[:CHECK_STEPS]]
        batches = [(self.tokens[i], self.labels[i])
                   for i in range(CHECK_STEPS)]
        del self.state, self.fn
        params0 = self.draw(self.k_w)
        want = reference(self.c, self.traffic, params0, batches, self.q)
        first = _host_norms(want["first"], self.slices)
        change = _host_norms(want["change"], self.slices)

        def read(got_losses, got_first, got_change, ef1, got_bits,
                 got_slots):
            out = gaps(got_losses, got_first, got_change, want, first,
                       change)
            out["ef1_err"] = host_diff_norm(ef1, want["ef1"])
            out["ef1_leaf_err"] = leaf_diff_norm(ef1, want["ef1"],
                                                 self.slices)
            out["bits_err"] = bits_gap(got_bits, want["bits"])
            out["routed_slots_err"] = max(
                abs(a - b) / b for a, b in zip(got_slots,
                                               want["routed_slots"]))
            return out

        got = read(losses, self.first_norms, self.change_norms, self.ef1,
                   bits, slots)
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
            readings[v] = read(alt["loss"],
                               _host_norms(alt["first"], self.slices),
                               _host_norms(alt["change"], self.slices),
                               alt["ef1"], alt["bits"], alt["routed_slots"])
        return checks, self.steps, failed, readings


def _host_norms(flat: np.ndarray, slices: dict) -> dict:
    return {n: float(np.sqrt(np.sum(np.square(flat[s], dtype=np.float64))))
            for n, s in slices.items()}


def host_diff_norm(a: np.ndarray, b: np.ndarray) -> float:
    """‖a − b‖ / ‖b‖ of two flat host vectors, in float64 sums."""
    a, b = a.astype(np.float32), b.astype(np.float32)
    return float(np.sqrt(np.sum(np.square(a - b, dtype=np.float64))
                         / np.sum(np.square(b, dtype=np.float64))))


#: entries both sides must hold for a leaf to count in ``leaf_diff_norm``: a
#: per-head vector (D, dt_bias, A_log) of a few entries reads rounding noise
LEAF_MIN_ENTRIES = 1024


def leaf_diff_norm(a: np.ndarray, b: np.ndarray, slices: dict) -> float:
    """Worst leaf of ‖a − b‖ / ‖b‖ over the entries both hold (nonzero):
    in the error feedback after one hop, the gradient that neither side
    sent. Leaves with fewer than ``LEAF_MIN_ENTRIES`` such entries, or
    whose ‖b‖ there is under a thousandth of the median leaf's (as
    ``bench.compare.norm_gap``), are left out."""
    def norm(x):
        return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))

    diff, want = {}, {}
    for n, s in slices.items():
        x, y = a[s].astype(np.float32), b[s].astype(np.float32)
        both = (x != 0) & (y != 0)
        if np.count_nonzero(both) >= LEAF_MIN_ENTRIES:
            diff[n], want[n] = norm((x - y)[both]), norm(y[both])
    med = float(np.median(list(want.values())))
    return max(diff[n] / want[n] for n in want
               if want[n] > 0 and want[n] >= 1e-3 * med)


def reference(c: dict, traffic: dict, params0, batches, q: int,
              mm_dtype=None) -> dict:
    seq = int(batches[0][0].shape[-1])
    return ref.train(params0, batches, steps=len(batches),
                     dims=ref_dims(c, seq), q=q, opt=traffic["optimizer"],
                     mm_dtype=mm_dtype)
