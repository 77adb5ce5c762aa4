"""Layer scopes (``repro.obs.scopes``) on compiled programs and host spans.

Every instruction that runs as a device op of the aggregation round, the
train step and the simulator round, compiled for the CPU, falls in a known
scope, and each layer issues work of its own; ``PhaseTimer`` spans reach
the profiler's trace. The round compiled for a described v5e is checked in
``tests/test_tpu_compile.py``.
"""

import dataclasses
import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.agg import compile_plan, execute
from repro.agg.device import ring_chain_plan
from repro.configs import PAPER, get_config
from repro.configs.base import ModelConfig
from repro.core.algorithms import AggConfig, AggKind
from repro.data.federated import partition_iid
from repro.data.synthetic import make_synthetic_mnist
from repro.fed.simulator import Simulator
from repro.obs import PhaseTimer, scopes
from repro.optim.optimizers import OptConfig
from repro.train.state import TrainConfig
from repro.train.step import build_train_step, init_state

K, D = 4, 4096
PC = dataclasses.replace(PAPER, num_clients=K)


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/repro.executor/while/body/repro.node_step/repro.kernel_layout"
     "/pad", scopes.KERNEL_LAYOUT),
    ("jit(f)/repro.grads/transpose(jvp(repro.grads))/dot_general",
     scopes.GRADS),
    ("jit(f)/jvp(repro.aggregate)/repro.tau_search/top_k",
     scopes.TAU_SEARCH),
    ("jit(f)/repro.grads/transpose(jvp(repro.moe))/dot_general",
     scopes.MOE),
    ("jit(f)/repro.executors/add", None),
    ("jit(f)/repro.unknown/add", None),
    ("jit(f)/add", None),
    ("", None),
])
def test_innermost_known_scope(op_name, want):
    assert scopes.innermost(op_name) == want


def test_scopes_in_keeps_the_nesting():
    path = "jit(f)/repro.aggregate/shard_map/repro.node_step/repro.tau_search"
    assert scopes.scopes_in(path) == (scopes.AGGREGATE, scopes.NODE_STEP,
                                      scopes.TAU_SEARCH)


# ---------------------------------------------------------------------------
# Programs compiled for the CPU
# ---------------------------------------------------------------------------

def _round_text(topq: str) -> str:
    cfg = AggConfig(kind=AggKind.CL_SIA, q=D // 100, topq_impl=topq,
                    kernel_mode="ref")
    fn = jax.jit(functools.partial(execute, cfg))
    return fn.lower(compile_plan(K), jnp.ones((K, D)), jnp.zeros((K, D)),
                    jnp.full((K,), 1.0 / K)).compile().as_text()


def _train_text(cfg=None) -> str:
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = cfg or ModelConfig(
        name="tiny", family="ssm", num_layers=2, d_model=32, num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=64, ssm_state=16, ssm_headdim=16,
        ssm_expand=2, ssm_chunk=16, ssm_conv=4, param_dtype="float32")
    tc = TrainConfig(agg=AggConfig(kind=AggKind.CL_SIA, q=1,
                                   topq_impl="exact"),
                     opt=OptConfig(name="adamw", lr=1e-3), q_frac=0.05,
                     agg_dtype="bfloat16", ef_dtype="bfloat16")
    toks = jnp.zeros((2, 32), jnp.int32)
    plan = ring_chain_plan(1)
    with jax.set_mesh(mesh):
        st = init_state(cfg, tc, mesh, jax.random.PRNGKey(0), topology=plan)
        step = jax.jit(build_train_step(cfg, tc, mesh, topology=plan))
        return step.lower(st, {"tokens": toks, "labels": toks}
                          ).compile().as_text()


@pytest.fixture(scope="module")
def fed():
    train = make_synthetic_mnist(jax.random.PRNGKey(0), K * 20)
    return partition_iid(jax.random.PRNGKey(2), train, K)


def _sim(fed):
    cfg = AggConfig(kind=AggKind.CL_TC_SIA, q=PC.q, q_global=PC.q_global,
                    q_local=PC.q_local)
    return Simulator(PC, cfg, fed, local_lr=PC.lr)


def _sim_text(fed) -> str:
    sim = _sim(fed)
    return jax.jit(sim.round_fn()).lower(
        sim.init(0), compile_plan(K)).compile().as_text()


PROGRAMS = {
    "round_threshold": (lambda fed: _round_text("threshold"),
                        {scopes.EXECUTOR, scopes.NODE_STEP,
                         scopes.TAU_SEARCH}),
    "round_exact": (lambda fed: _round_text("exact"),
                    {scopes.EXECUTOR, scopes.NODE_STEP, scopes.TAU_SEARCH}),
    "train_step": (lambda fed: _train_text(),
                   {scopes.GRADS, scopes.AGGREGATE, scopes.NODE_STEP,
                    scopes.TAU_SEARCH, scopes.OPTIMIZER}),
    "train_step_moe": (lambda fed: _train_text(
                           get_config("nemotron3-nano-30b-a3b", smoke=True)),
                       {scopes.GRADS, scopes.MOE, scopes.AGGREGATE,
                        scopes.NODE_STEP, scopes.TAU_SEARCH,
                        scopes.OPTIMIZER}),
    "sim_round": (_sim_text,
                  {scopes.GRADS, scopes.AGGREGATE, scopes.EXECUTOR,
                   scopes.NODE_STEP, scopes.TAU_SEARCH, scopes.EVAL}),
}


@pytest.fixture(scope="module")
def compiled(fed):
    return {name: scopes.instructions(make(fed))
            for name, (make, _) in PROGRAMS.items()}


def _working(instrs):
    """The instructions that run as device ops and do work. A copy of an
    input into the result (an output that is the input itself, as the
    simulator's ``tcs_prev``) is the runtime's, no layer's."""
    params = {i.name for i in instrs if i.opcode == "parameter"}
    return [i for i in instrs if i.executed and i.opcode != "parameter"
            and not (i.opcode == "copy" and set(i.operands) <= params)]


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_every_device_op_falls_in_a_known_scope(compiled, program):
    ops = _working(compiled[program])
    assert len(ops) > 20
    unscoped = [(i.name, i.opcode, i.op_name) for i in ops
                if i.scope is None]
    assert not unscoped


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_each_layer_issues_work(compiled, program):
    found = {i.scope for i in _working(compiled[program])}
    assert found == PROGRAMS[program][1]


def test_train_top_k_is_the_tau_search_of_the_aggregation(compiled):
    topk = [i for i in _working(compiled["train_step"])
            if "top_k" in i.op_name or i.opcode == "sort"]
    assert topk
    for i in topk:
        assert i.scope == scopes.TAU_SEARCH and not i.inherited
        assert scopes.AGGREGATE in i.path
    # the model's matmuls, forward and backward, are the local gradients
    dots = [i for i in _working(compiled["train_step"]) if i.opcode == "dot"]
    assert dots and all(i.scope == scopes.GRADS and not i.inherited
                        for i in dots)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_inherited_scopes_are_marked(compiled, program):
    """Only an instruction with no op_name of its own takes a neighbour's
    scope, and each layer scopes work of its own."""
    ops = _working(compiled[program])
    assert all(i.inherited == (i.op_name == "") for i in ops)
    own = [i for i in ops if not i.inherited]
    assert all(i.path == scopes.scopes_in(i.op_name) for i in own)
    assert {i.scope for i in own} == PROGRAMS[program][1]


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir: str) -> set:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    return {ev.name for plane in ProfileData.from_file(files[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("keep", [True, False])
def test_phase_timer_annotates_the_profiler(tmp_path, keep):
    timer = PhaseTimer()
    with jax.profiler.trace(str(tmp_path)):
        with timer.phase("work", track="bench", keep=keep) as span:
            jnp.ones(4).block_until_ready()
    assert "repro.bench.work" in _host_events(str(tmp_path))
    assert span.dur_s > 0 and span.t0_s >= 0
    assert timer.spans == ([span] if keep else [])
    assert list(timer.totals()) == (["work"] if keep else [])


def test_simulator_run_phases_reach_the_profiler(fed, tmp_path):
    from repro.obs import TraceCollector
    sim = _sim(fed)
    out_file = str(tmp_path / "run.jsonl")
    with TraceCollector(out_file) as col:
        with jax.profiler.trace(str(tmp_path)):
            out = sim.run(2, flush_every=1, collector=col)
    assert len(out["loss"]) == 2
    want = {f"repro.simulator.{p}" for p in ("plan", "dispatch", "flush")}
    assert want <= _host_events(str(tmp_path))
    with open(out_file) as f:
        records = [json.loads(line) for line in f]
    # each flush is a span of its own, not a phase of the next round
    flushes = [r for r in records if r["kind"] == "span"]
    assert [r["name"] for r in flushes] == ["flush", "flush"]
    assert all(r["dur_s"] > 0 for r in flushes)
    rounds = [r for r in records if r["kind"] == "round"]
    assert len(rounds) == 2
    assert all(set(r["phases"]) == {"plan", "dispatch"} for r in rounds)
