"""Simulator rounds: the paper's §VI experiment, round after round.

Set-up draws the data set from the seed, builds one ``Simulator``, jits its
round (``Simulator.round_fn``, the program ``Simulator.run`` compiles) and
drives it from the seed through its first three rounds on the chain plan,
reading what the reference needs from them; the window goes on with the
same compiled round and state. Each timed unit is ``flush_every`` rounds
as ``Simulator.run`` runs them: dispatched one after another, their logs
kept on the device, then fetched in one ``device_get`` and reduced to the
loss, bits and nnz curves.

``Simulator.run`` itself is not called in the window: it builds a new jit
of the round on every call, and the round holds the training set as a
constant (1 GB of executable at 60,000 samples, too large for the compile
cache), so every call compiles again.

The round runs as ``Simulator.run`` runs it: the chain plan, CL-TC-SIA
with exact Top-Q as the configuration states, and JAX's default matrix
precision, which nothing in the program sets.

Correctness (``bench/refs/logreg_chain.py``, float64): the first update
as the model gets it (after one round) and the model's change after three
rounds, each by its worst leaf (``w`` and ``b``), and the §V bits of each
of the first three rounds. The loss of those rounds is read too; the
limits file names the numbers compared.
"""

from __future__ import annotations

import jax
import numpy as np

from bench import data
from bench.compare import norm_gap
from bench.refs import logreg_chain as ref
from repro.agg import compile_plan
from repro.configs.paper_mnist import PaperConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import FederatedData
from repro.fed.simulator import Simulator

CHECK_ROUNDS = 3


class Driver:
    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int,
                 devices, seconds: float = 0.0):
        c = config
        self.c, self.traffic, self.limits = c, traffic, limits
        self.seed = seed
        # Simulator.init keys its stream with PRNGKey(seed): a 31-bit seed
        self.sim_seed = int(np.random.SeedSequence(
            int(seed) % (1 << 128)).generate_state(1)[0] >> 1)
        self.k = int(c["num_clients"])
        self.per_client = int(c["train_samples"]) // self.k
        self.pc = PaperConfig(input_dim=int(c["input_dim"]),
                              num_classes=int(c["num_classes"]), d=int(c["d"]),
                              num_clients=self.k,
                              batch_size=int(c["batch_size"]),
                              lr=float(c["lr"]), q=int(c["q"]),
                              q_local=int(c["q_local"]),
                              q_global=int(c["q_global"]),
                              omega=int(c["omega"]))
        self.agg = AggConfig(kind=AggKind.CL_TC_SIA, q=self.pc.q,
                             q_global=self.pc.q_global,
                             q_local=self.pc.q_local, omega=self.pc.omega,
                             topq_impl="exact")
        self.flush_every = int(traffic["flush_every"])
        self.rounds = 0
        self.curves = {"loss": [], "bits": [], "nnz": []}

    def setup(self) -> None:
        x, y = data.mnist_like(data.seed_key(self.seed), clients=self.k,
                               per_client=self.per_client,
                               dim=self.pc.input_dim,
                               classes=self.pc.num_classes)
        self.x, self.y = jax.block_until_ready((x, y))
        self.sim = Simulator(self.pc, self.agg, FederatedData(x=x, y=y),
                             local_lr=self.pc.lr)
        self.fn = jax.jit(self.sim.round_fn())
        self.plan = compile_plan(self.k)
        self.state = self.sim.init(self.sim_seed)
        models = [np.asarray(self.state.flat_w, np.float64)]
        for _ in range(CHECK_ROUNDS):
            self._rounds(1)
            models.append(np.asarray(self.state.flat_w, np.float64))
        self.models = models

    def _rounds(self, n: int) -> None:
        """``n`` rounds as ``Simulator.run`` runs them, then one flush."""
        logs = []
        for _ in range(n):
            self.state, log = self.fn(self.state, self.plan, None)
            logs.append(log)
        for log in jax.device_get(logs):
            self.curves["loss"].append(float(log.loss))
            self.curves["bits"].append(float(sum(
                np.sum(np.asarray(s.bits)) for s in log.stats)))
            self.curves["nnz"].append(float(sum(
                np.sum(np.asarray(s.nnz_out)) for s in log.stats)))

    def step(self) -> int:
        self._rounds(self.flush_every)
        self.rounds += self.flush_every
        return self.flush_every

    def shape(self) -> dict:
        return {"unit": "sim_round", "clients": self.k, "hops": self.k,
                "d": self.pc.d, "entry_bytes": 4,
                "batch": self.pc.batch_size, "input_dim": self.pc.input_dim,
                "classes": self.pc.num_classes,
                "samples": self.k * self.per_client}

    # -- correctness ----------------------------------------------------------
    def _reference(self, bf16: bool = False) -> dict:
        pc = self.pc
        return ref.run(np.asarray(self.x), np.asarray(self.y),
                       seed=self.sim_seed, rounds=CHECK_ROUNDS,
                       batch=pc.batch_size, lr=pc.lr, q_local=pc.q_local,
                       q_global=pc.q_global, classes=pc.num_classes,
                       omega=pc.omega, bf16=bf16)

    def check(self, variants=()):
        """→ (checks, attempted, failed, readings): ``readings["program"]``
        every number read off the program, ``readings["control"]`` the
        same numbers read off the reference in bfloat16."""
        want = self._reference()
        got = self.gaps(self.curves["loss"][:CHECK_ROUNDS],
                        self.curves["bits"][:CHECK_ROUNDS], self.models,
                        want)
        checks = [(n, v, self.limits[n]) for n, v in got.items()
                  if n in self.limits]
        failed = int(any(v > lim for _, v, lim in checks))
        readings = {"program": got}
        if "control" in variants:
            c = self._reference(bf16=True)
            readings["control"] = self.gaps(c["loss"], c["bits"], c["w"],
                                            want)
        return checks, self.rounds, failed, readings

    def gaps(self, loss, bits, models, want) -> dict:
        wd = self.pc.input_dim * self.pc.num_classes
        leaves = {"w": slice(0, wd), "b": slice(wd, self.pc.d)}

        def norms(v):
            return {n: float(np.linalg.norm(np.asarray(v)[s]))
                    for n, s in leaves.items()}

        w_loss = np.asarray(want["loss"])
        w0 = want["w"][0]
        return {
            "loss_err": float(np.max(np.abs(np.asarray(loss) - w_loss)
                                     / np.abs(w_loss))),
            "first_update_err": norm_gap(norms(models[1] - models[0]),
                                         norms(want["w"][1] - w0)),
            "change3_err": norm_gap(norms(models[3] - models[0]),
                                    norms(want["w"][3] - w0)),
            "bits_err": float(np.max(np.abs(np.asarray(bits)
                                            - np.asarray(want["bits"])))),
        }
