#!/usr/bin/env python3
"""Run the main path once on a TPU and check it against the unfused path.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip:

A. The paper's model (§VI, ``configs/paper_mnist.py``: d=7850, K=28, Q=78,
   Q_G=70, Q_L=8) through ``Simulator``: the chain and a routed
   Walker-delta tree (4 planes x 7), the five sparse algorithms, 5 rounds
   each, once with the compiled Pallas kernels (``kernel_mode="auto"``)
   and once with the unfused jnp path (``"never"``). Per-round §V bits and
   nnz must be equal; the loss agrees to ``LOSS_RTOL``.
B. One aggregation round at d=2^24 — one of eight ring segments of a
   ~130M-parameter flat gradient — with K=8 clients through
   ``compile_plan`` → ``execute``: five algorithms x {exact, threshold,
   threshold + histogram τ} on the chain, fused against unfused (phase A
   carries the routed trees). §V counters must be equal, aggregate and EF
   agree to
   ``ROUND_RTOL``, and the fused round's compiled HLO must hold
   ``tpu_custom_call`` (no quiet fallback to jnp or interpret mode).
C. ``repro.launch.train`` on mamba2-130m at its published widths
   (24 layers, d_model 768, vocab 50280) on a 1x1 mesh, ring topology,
   CL-SIA, 3 steps: loss and ``agg_bits`` must be finite.

Four chips (``--chips 4``): ``execute_sharded`` over ``client_mesh(4)``
against host ``execute`` (five algorithms x chain/tree, bitwise but for
the float ``err_sq`` diagnostic, held to ``ROUND_RTOL``),
``execute_nested_sharded`` of ``pod_ring_nested(2, 2)`` against
``execute_nested`` (bitwise), and ``Simulator(backend="device")`` with K=4
for 5 rounds, compiled once, with host-equal §V bits.

Earlier lines report phase times, compile seconds and peak device memory.
The last line of standard output is ``{"ok": true, "device": {...}}``.
The script exits nonzero, printing no such line, when JAX finds no TPU.
JAX's persistent compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else to ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import functools
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax                                         # noqa: E402
import jax.numpy as jnp                            # noqa: E402
import numpy as np                                 # noqa: E402

from repro.agg import compile_plan, execute        # noqa: E402
from repro.agg.schedule import common_shape        # noqa: E402
from repro.configs import PAPER                    # noqa: E402
from repro.core import sparsify as sp              # noqa: E402
from repro.core.algorithms import AggConfig, AggKind   # noqa: E402
from repro.data.federated import partition_iid     # noqa: E402
from repro.data.synthetic import make_synthetic_mnist  # noqa: E402
from repro.fed.simulator import Simulator          # noqa: E402
from repro.fed.topology import TreeTopology        # noqa: E402
from repro.topo.graph import walker_delta          # noqa: E402

ALGOS = (AggKind.SIA, AggKind.RE_SIA, AggKind.CL_SIA, AggKind.TC_SIA,
         AggKind.CL_TC_SIA)
SPARSIFIERS = {
    "exact": dict(topq_impl="exact"),
    "threshold": dict(topq_impl="threshold"),
    "threshold-hist": dict(topq_impl="threshold", tau_impl="hist",
                           hist_rounds=2),
}
# The fused kernels and XLA:TPU compute the same float expressions, but a
# compiler may still round w·g+e or (1−m)·s differently: one ulp per op,
# compounded over at most K hops, moves a round's output by ~K·2^-24
# relative. The loss after 5 rounds of SGD on the paper's model moves by
# the same order.
ROUND_RTOL = 1e-5
LOSS_RTOL = 1e-5
# how a compiled Mosaic (Pallas TPU) kernel appears in HLO and StableHLO
KERNEL_MARK = "tpu_custom_call"


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


@contextlib.contextmanager
def phase(name: str):
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    print(f"[{name}] done: {time.perf_counter() - t0:.1f} s wall, "
          f"{_COMPILE_S[0] - c0:.1f} s compiling, "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)


@contextlib.contextmanager
def lowered_ir():
    """Collect the StableHLO of every program lowered inside the block.

    Yields a list that, on exit, holds one module text per lowering — how
    a phase that only calls a public entry point shows whether its
    compiled programs carry the Pallas kernels (``tpu_custom_call``)."""
    texts: list = []
    with tempfile.TemporaryDirectory(dir=REPO, prefix=".ir_dump_") as d:
        jax.config.update("jax_dump_ir_to", d)
        try:
            yield texts
        finally:
            jax.config.update("jax_dump_ir_to", "")
        for name in sorted(os.listdir(d)):
            with open(os.path.join(d, name)) as f:
                texts.append(f.read())


def has_kernels(texts) -> bool:
    return any(KERNEL_MARK in t for t in texts)


def same(a, b) -> bool:
    return bool(jnp.array_equal(a, b))


def close(a, b, rtol: float) -> bool:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)
    return bool(jnp.max(jnp.abs(a - b)) <= rtol * scale)


def tc_mask(cfg: AggConfig, d: int, key):
    if cfg.kind not in (AggKind.TC_SIA, AggKind.CL_TC_SIA):
        return None
    return sp.topq_mask(jax.random.normal(key, (d,)), cfg.q_global)


# ---------------------------------------------------------------------------
# A. the paper's model through Simulator
# ---------------------------------------------------------------------------

def phase_a(rounds: int = 5) -> None:
    pc = PAPER
    k = pc.num_clients
    train = make_synthetic_mnist(jax.random.PRNGKey(0), k * 150)
    fed = partition_iid(jax.random.PRNGKey(2), train, k)
    topos = (("chain", None), ("walker-delta", TreeTopology(walker_delta(4, 7))))
    for topo_name, topo in topos:
        for kind in ALGOS:
            out = {}
            for mode in ("auto", "never"):
                agg = AggConfig(kind=kind, q=pc.q, q_global=pc.q_global,
                                q_local=pc.q_local, kernel_mode=mode)
                sim = Simulator(pc, agg, fed, local_lr=pc.lr,
                                tree_topology=topo)
                with lowered_ir() as ir:
                    out[mode] = sim.run(rounds, seed=0)
                check(has_kernels(ir) == (mode == "auto"),
                      f"A {topo_name}/{kind.value}/{mode}: kernels "
                      f"lowered = {has_kernels(ir)}")
                check(sim.trace_counter.count == 1,
                      f"A {topo_name}/{kind.value}/{mode}: "
                      f"{sim.trace_counter.count} traces")
            f, u = out["auto"], out["never"]
            check(f["bits"] == u["bits"],
                  f"A {topo_name}/{kind.value}: bits {f['bits']} vs "
                  f"{u['bits']}")
            check(f["nnz"] == u["nnz"],
                  f"A {topo_name}/{kind.value}: nnz {f['nnz']} vs {u['nnz']}")
            loss_f, loss_u = np.asarray(f["loss"]), np.asarray(u["loss"])
            check(np.all(np.isfinite(loss_f)), f"A {kind.value}: loss nan")
            np.testing.assert_allclose(loss_f, loss_u, rtol=LOSS_RTOL,
                                       err_msg=f"A {topo_name}/{kind.value}")
            print(f"[A] {topo_name:12s} {kind.value:9s} bits/round "
                  f"{f['bits'][-1]:.0f} nnz {f['nnz'][-1]:.0f} loss "
                  f"{loss_f[-1]:.6f} loss bitwise "
                  f"{bool(np.array_equal(loss_f, loss_u))}", flush=True)


# ---------------------------------------------------------------------------
# B. one aggregation round at model-scale d
# ---------------------------------------------------------------------------

def phase_b(d: int = 1 << 24, k: int = 8) -> None:
    q = d // 100
    key = jax.random.PRNGKey(11)
    g = jax.random.normal(key, (k, d), jnp.float32)
    e = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (k, d))
    w = jnp.ones((k,), jnp.float32)
    part = jnp.ones((k,), jnp.float32).at[3].set(0.0)   # one straggler
    plan = compile_plan(k)
    # Every algorithm x sparsifier on the chain (W = 1 lane per level). A
    # routed tree's round (W = 3 lanes) takes ~3 min to compile for the chip
    # at this d, twice per cell: phase A runs the five algorithms' tree
    # rounds at the paper's d instead, and tests/test_tpu_compile.py
    # compiles the W = 8 kernels at this d.
    cells = [(kind, s) for kind in ALGOS for s in SPARSIFIERS]
    masks = {kind: tc_mask(AggConfig(kind=kind, q=q), d,
                           jax.random.fold_in(key, 2)) for kind in ALGOS}

    bitwise_all = True
    for kind, sname in cells:
        tag = f"B {kind.value}/{sname}"
        res = {}
        for mode in ("auto", "never"):
            cfg = AggConfig(kind=kind, q=q, kernel_mode=mode,
                            **SPARSIFIERS[sname])
            args = (plan, g, e, w)
            kw = dict(global_mask=masks[kind], participate=part)
            t0 = time.perf_counter()
            exe = jax.jit(functools.partial(execute, cfg)).lower(
                *args, **kw).compile()
            secs = time.perf_counter() - t0
            fused = has_kernels([exe.as_text()])
            check(fused == (mode == "auto"),
                  f"{tag}/{mode}: tpu_custom_call in the compiled HLO = "
                  f"{fused}")
            t0 = time.perf_counter()
            res[mode] = jax.block_until_ready(exe(*args, **kw))
            print(f"[B] {tag}/{mode}: compile {secs:.1f} s, first round "
                  f"{1e3 * (time.perf_counter() - t0):.1f} ms", flush=True)
            del exe
        f, u = res["auto"], res["never"]
        for field in ("nnz_out", "nnz_global", "nnz_local", "bits"):
            check(same(getattr(f.stats, field), getattr(u.stats, field)),
                  f"{tag}: stats.{field} differ")
        check(close(f.aggregate, u.aggregate, ROUND_RTOL),
              f"{tag}: aggregate beyond rtol {ROUND_RTOL}")
        check(close(f.e_new, u.e_new, ROUND_RTOL),
              f"{tag}: EF beyond rtol {ROUND_RTOL}")
        bitwise = same(f.aggregate, u.aggregate) and same(f.e_new, u.e_new)
        bitwise_all &= bitwise
        print(f"[B] {tag}: counters equal, nnz "
              f"{int(jnp.sum(f.stats.nnz_out))}, bits "
              f"{float(jnp.sum(f.stats.bits)):.0f}, bitwise {bitwise}",
              flush=True)
        del res, f, u
    print(f"[B] fused == unfused bitwise in every cell: {bitwise_all}")
    _err_sq_on_chip(g[:2], e[:2])


def _err_sq_on_chip(g, e) -> None:
    """The in-kernel pinned-order ‖e'‖² against its jnp contract."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    lanes = g.shape[0]
    ones = jnp.ones((lanes,), jnp.float32)
    tau = jnp.full((lanes,), 1.5, jnp.float32)
    out = jax.jit(functools.partial(kops.sparsify_ef_level, with_err=True))(
        g, e, None, ones, tau, ones)
    want = jax.jit(kref.ref_err_sq_level)(out[1])
    check(same(out[3], want), f"B err_sq: kernel {out[3]} vs ref {want}")
    print(f"[B] pinned-order err_sq kernel == reference bitwise: {out[3]}")


# ---------------------------------------------------------------------------
# C. the train step at published widths
# ---------------------------------------------------------------------------

def phase_c(steps: int = 3) -> None:
    from repro.launch import train
    argv = ["--arch", "mamba2-130m", "--mesh", "1x1", "--topology", "ring",
            "--agg", "cl_sia", "--steps", str(steps)]
    with lowered_ir() as ir:
        metrics = train.main(argv)
    loss, bits = float(metrics["loss"]), float(metrics["agg_bits"])
    check(math.isfinite(loss) and math.isfinite(bits),
          f"C: loss {loss}, agg_bits {bits}")
    print(f"[C] mamba2-130m loss {loss:.4f} agg_bits {bits:.4e}, kernels "
          f"in the train step: {has_kernels(ir)}", flush=True)


# ---------------------------------------------------------------------------
# four chips: the sharded paths
# ---------------------------------------------------------------------------

def _four_chip_inputs(d: int, k: int):
    key = jax.random.PRNGKey(21)
    g = jax.random.normal(key, (k, d), jnp.float32)
    e = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (k, d))
    w = jnp.ones((k,), jnp.float32)
    part = jnp.ones((k,), jnp.float32).at[1].set(0.0)
    return g, e, w, part, jax.random.fold_in(key, 2)


def sharded_cells(kinds=ALGOS, d: int = 1 << 14, k: int = 4) -> None:
    """Client-per-rank ``execute_sharded`` against host ``execute``.

    The aggregate, EF and the integer §V counters must be bitwise equal.
    ``err_sq`` (‖e'‖² with the default ``err_sq_mode="jnp"``) is a float
    reduction whose association XLA picks per program, so it is held to
    ``ROUND_RTOL``; ``err_sq_mode="kernel"`` is the pinned-order form."""
    from repro.agg.device import client_mesh, execute_sharded
    mesh = client_mesh(k)
    g, e, w, part, mkey = _four_chip_inputs(d, k)
    routed = walker_delta(2, k // 2)
    shape = common_shape([compile_plan(k), compile_plan(routed)])
    plans = {"chain": compile_plan(k, pad_to=shape),
             "walker-delta": compile_plan(routed, pad_to=shape)}
    for kind in kinds:
        cfg = AggConfig(kind=kind, q=d // 100)
        gm = tc_mask(cfg, d, mkey)
        host = jax.jit(functools.partial(execute, cfg))
        dev = jax.jit(functools.partial(execute_sharded, cfg, mesh=mesh))
        for pname, plan in plans.items():
            want = host(plan, g, e, w, global_mask=gm, participate=part)
            got = dev(plan, g, e, w, global_mask=gm, participate=part)
            tag = f"4chip {kind.value}/{pname}"
            check(same(want.aggregate, got.aggregate), f"{tag}: aggregate")
            check(same(want.e_new, got.e_new), f"{tag}: EF")
            for field in ("nnz_out", "nnz_local", "bits"):
                check(same(getattr(want.stats, field),
                           getattr(got.stats, field)),
                      f"{tag}: stats.{field}")
            check(close(got.stats.err_sq, want.stats.err_sq, ROUND_RTOL),
                  f"{tag}: stats.err_sq beyond rtol {ROUND_RTOL}")
            print(f"[4] execute_sharded == execute bitwise: {tag} (err_sq "
                  f"bitwise {same(want.stats.err_sq, got.stats.err_sq)})",
                  flush=True)
        check(dev._cache_size() == 1,
              f"4chip {kind.value}: {dev._cache_size()} compiles")


def nested_cells(kinds=ALGOS, d: int = 1 << 14, k: int = 4) -> None:
    """``execute_nested_sharded`` of the pod×data plan against host
    ``execute_nested``: aggregate, stage EF and counters bitwise."""
    from repro.agg.device import client_mesh, execute_nested_sharded
    from repro.agg.nested import execute_nested, pod_ring_nested
    mesh = client_mesh(k)
    g, e, w, part, mkey = _four_chip_inputs(d, k)
    nested = pod_ring_nested(2, k // 2)
    for kind in kinds:
        cfg = AggConfig(kind=kind, q=d // 100)
        gm = tc_mask(cfg, d, mkey)
        want = jax.jit(functools.partial(execute_nested, cfg))(
            nested, g, e, w, global_mask=gm, participate=part)
        got = jax.jit(functools.partial(
            execute_nested_sharded, cfg, mesh=mesh))(
                nested, g, e, w, global_mask=gm, participate=part)
        tag = f"4chip nested {kind.value}"
        check(same(want.aggregate, got.aggregate), f"{tag}: aggregate")
        check(same(want.e_new, got.e_new), f"{tag}: EF")
        for a, b in zip(want.stage_e_new, got.stage_e_new):
            check(same(a, b), f"{tag}: stage EF")
        for a, b in zip((want.stats,) + tuple(want.stage_stats),
                        (got.stats,) + tuple(got.stage_stats)):
            check(same(a.bits, b.bits) and same(a.nnz_out, b.nnz_out),
                  f"{tag}: stage stats")
        print(f"[4] execute_nested_sharded == execute_nested bitwise: "
              f"{tag}", flush=True)


def device_simulator(k: int = 4, rounds: int = 5) -> None:
    """``Simulator(backend="device")`` compiles its round once and sends
    the host backend's §V bits."""
    pc = dataclasses.replace(PAPER, num_clients=k)
    train = make_synthetic_mnist(jax.random.PRNGKey(0), k * 150)
    fed = partition_iid(jax.random.PRNGKey(2), train, k)
    agg = AggConfig(kind=AggKind.CL_SIA, q=pc.q)
    runs = {}
    for backend in ("host", "device"):
        sim = Simulator(pc, agg, fed, local_lr=pc.lr, backend=backend)
        runs[backend] = sim.run(rounds, seed=0)
        check(sim.trace_counter.count == 1,
              f"4chip {backend} simulator: {sim.trace_counter.count} "
              f"compiles in {rounds} rounds")
    check(runs["host"]["bits"] == runs["device"]["bits"],
          "4chip simulator: device bits differ from host")
    print(f"[4] Simulator(backend='device') K={k}: {rounds} rounds, "
          f"1 compile, bits equal to host", flush=True)


def four_chips() -> None:
    # bitwise equality of the sharded lowerings does not depend on d; a
    # small d (2^14) keeps the 22 programs' compile time inside a short
    # four-chip call
    with phase("4chip sharded"):
        sharded_cells()
    with phase("4chip nested"):
        nested_cells()
    with phase("4chip device simulator"):
        device_simulator()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              f"run", file=sys.stderr)
        return 2
    faulthandler.enable()       # a native crash still names its Python line
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        with phase("A paper model"):
            phase_a()
        with phase("B round d=2^24"):
            phase_b()
        with phase("C mamba2-130m train"):
            phase_c()
    print(f"total {time.perf_counter() - t0:.1f} s, compiling "
          f"{_COMPILE_S[0]:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
