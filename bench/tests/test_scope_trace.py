"""The join of a device trace with the timed program's compiled text.

The first tests build profiler planes by hand around a program compiled on
the CPU; the last read a trace recorded on a TPU v5e:
``data/round_chain8_scoped/`` holds the ``.xplane.pb`` of a 5-second window
of ``mamba2-130m.round-chain8-thr`` and the compiled text of its round,
written by ``python bench/scope_trace.py record``.

    PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import scope_trace, trace  # noqa: E402

RECORDED = os.path.join(HERE, "data", "round_chain8_scoped")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@pytest.fixture(scope="module")
def program():
    import jax
    import jax.numpy as jnp
    from repro.agg import compile_plan, execute
    from repro.core.algorithms import AggConfig, AggKind
    from repro.obs import scopes
    cfg = AggConfig(kind=AggKind.CL_SIA, q=40, topq_impl="threshold",
                    kernel_mode="ref")
    text = jax.jit(functools.partial(execute, cfg)).lower(
        compile_plan(4), jnp.ones((4, 4096)), jnp.zeros((4, 4096)),
        jnp.full((4,), 0.25)).compile().as_text()
    by_scope, inherited = {}, {}
    for i in scopes.instructions(text):
        if i.executed and i.opcode != "parameter" and i.scope:
            if i.inherited:
                inherited.setdefault(i.scope, i.name)
            elif i.opcode == "fusion":
                by_scope.setdefault(i.scope, i.name)
    return text, by_scope, inherited


def _planes(module: str, ops: list, other: list = ()) -> list:
    """One chip running ``module`` once over [0, 1000) ns with ``ops``
    (name, start, dur) inside, and ``other`` ops in a second module run
    over [1000, 1200); one timed unit spans both."""
    ev = [Event(f"%{n} = f32[] fusion()", s, d) for n, s, d in ops]
    ev += [Event(f"%{n} = f32[] fusion()", s, d) for n, s, d in other]
    modules = [Event(f"{module}(42)", 0, 1000),
               Event("jit_other(7)", 1000, 200)]
    return [Plane("/device:TPU:0", [Line(scope_trace.MODULES_LINE, modules),
                                    Line(trace.OPS_LINE, ev)]),
            Plane("/host:CPU", [Line("python",
                                     [Event(trace.UNIT_EVENT, 0, 1200)])])]


def test_ops_are_booked_to_their_innermost_scope(program):
    from repro.obs import scopes
    text, by_scope, _ = program
    ex, tau = by_scope[scopes.EXECUTOR], by_scope[scopes.TAU_SEARCH]
    st = scope_trace.join(_planes(scope_trace.module_name(text),
                                  [(ex, 0, 300), (tau, 300, 500)]), text, 1)
    assert st.units == 1
    assert st.seconds(scopes.EXECUTOR) == pytest.approx(300e-9)
    assert st.seconds(scopes.TAU_SEARCH) == pytest.approx(500e-9)
    # the τ search runs under the node step, under the executor
    assert st.seconds(scopes.EXECUTOR, inclusive=True) == pytest.approx(
        800e-9)
    assert st.seconds(None) == 0


def test_a_failed_join_is_unscoped_never_misbooked(program):
    from repro.obs import scopes
    text, by_scope, _ = program
    ex = by_scope[scopes.EXECUTOR]
    st = scope_trace.join(_planes(
        scope_trace.module_name(text),
        [(ex, 0, 100), ("not_in_the_text.3", 100, 200)],
        other=[(ex, 1000, 150)]), text, 1)
    assert st.seconds(scopes.EXECUTOR) == pytest.approx(100e-9)
    assert st.seconds(None) == pytest.approx(350e-9)
    # the whole trace, joined with the text of another program
    other = text.replace(scope_trace.module_name(text), "jit_elsewhere", 1)
    st = scope_trace.join(_planes(scope_trace.module_name(text),
                                  [(ex, 0, 100)]), other, 1)
    assert st.seconds(None) == pytest.approx(100e-9)


def test_partition_covers_every_op(program):
    from repro.obs import scopes
    text, by_scope, _ = program
    ops = [(n, 100 * i, 100) for i, n in enumerate(by_scope.values())]
    st = scope_trace.join(_planes(scope_trace.module_name(text), ops,
                                  other=[(ops[0][0], 1000, 50)]), text, 1)
    part = st.partition([scopes.EXECUTOR, scopes.NODE_STEP])
    assert sum(part.values()) == pytest.approx(
        (100 * len(ops) + 50) * 1e-9)
    incl = st.partition([scopes.EXECUTOR], inclusive=True)
    assert incl["unscoped"] == pytest.approx(50e-9)


def test_inherited_time_is_reported_apart(program):
    from repro.obs import scopes
    text, by_scope, inherited = program
    scope, made = next(iter(inherited.items()))
    own = by_scope[scope]
    st = scope_trace.join(_planes(scope_trace.module_name(text),
                                  [(own, 0, 300), (made, 300, 200)]), text, 1)
    assert st.seconds(scope) == pytest.approx(500e-9)
    assert st.seconds(scope, inherited_only=True) == pytest.approx(200e-9)
    assert [o.inherited for o in st.ops] == [False, True]
    assert st.seconds(scopes.EVAL, inherited_only=True) == 0


def test_program_text_refuses_the_persistent_cache(tmp_path):
    import jax
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        with pytest.raises(RuntimeError, match="persistent compile cache"):
            scope_trace.program_text(object())
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_stripped_programs_compare_equal_but_for_metadata(program):
    text, _, _ = program
    moved = text.replace('op_name="jit(', 'op_name="jit(renamed/', 3)
    assert moved != text
    assert scope_trace.stripped(moved) == scope_trace.stripped(text)
    assert "metadata={" not in scope_trace.stripped(text)


# ---------------------------------------------------------------------------
# A trace recorded on a TPU v5e
# ---------------------------------------------------------------------------

ROUND_SCOPES = ("repro.executor", "repro.node_step", "repro.tau_search",
                "repro.kernel_layout")


@pytest.fixture(scope="module")
def recorded():
    return scope_trace.load(RECORDED)


@pytest.mark.parametrize("kernel,scope,per_hop", [
    ("cl_fuse_level_pallas", "repro.node_step", 1),
    ("count_ge_fused_level_pallas", "repro.tau_search", 3),
])
def test_recorded_kernels_join_their_layers(recorded, kernel, scope,
                                            per_hop):
    ops = [o for o in recorded.ops if o.name.rsplit(".", 1)[0] == kernel]
    # eight hops a round
    assert len(ops) == per_hop * 8 * recorded.units
    assert all(o.path[-1:] == (scope,) and not o.inherited for o in ops)


def test_recorded_round_is_scoped(recorded):
    part = recorded.partition(ROUND_SCOPES)
    assert part["unscoped"] < 0.01 * recorded.trace.busy_s
    assert sum(part.values()) == pytest.approx(recorded.trace.busy_s,
                                               rel=0.01)
    assert all(part[s] > 0 for s in ROUND_SCOPES)


def test_recorded_round_inherits_little(recorded):
    """The ops the compiler made (copies, a select fusion) take a
    neighbour's scope; the program's own scopes cover the rest."""
    inherited = {s: recorded.seconds(s, inherited_only=True)
                 for s in ROUND_SCOPES}
    assert 0 < sum(inherited.values()) < 0.05 * recorded.trace.busy_s
    assert inherited["repro.kernel_layout"] == 0
    assert inherited["repro.tau_search"] == 0
