"""The trace reduction on a trace recorded on a TPU v5e.

``data/round_chain8.xplane.pb`` is the profiler trace of a ``--trace 1`` run
of ``mamba2-130m.round-chain8-thr`` with a 5-second window: 25 rounds of
the K=8 CL-SIA chain at d = 16,132,152.

    PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace  # noqa: E402

TRACE = os.path.join(HERE, "data", "round_chain8.xplane.pb")
ROUNDS = 25


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return trace.reduce_planes(ProfileData.from_file(TRACE).planes, 1)


def test_window_is_the_units_span(recorded):
    units = sum(1 for *_, n in recorded.host if n == trace.UNIT_EVENT)
    assert units == ROUNDS
    assert recorded.window_s == pytest.approx(5.186406169)
    assert 0.98 < recorded.busy_s / recorded.window_s < 1.0


def test_kernels_are_found_by_their_names(recorded):
    count = collections.Counter(o.name for o in recorded.ops)
    # one cl_fuse and three count passes per hop, eight hops a round
    assert count["cl_fuse_level_pallas"] == 8 * ROUNDS
    assert count["count_ge_fused_level_pallas"] == 3 * 8 * ROUNDS


def test_only_control_flow_nests(recorded):
    nested = {o.name for o in recorded.ops if o.self_ns < o.dur}
    assert nested == {"while"}
    assert all(o.self_ns >= 0 for o in recorded.ops)
    assert recorded.total_op_seconds() == pytest.approx(recorded.busy_s,
                                                        rel=1e-3)


def test_breakdown_orders_ops_by_self_time(recorded):
    b = recorded.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[:3] == ["pad_reduce_fusion", "count_ge_fused_level_pallas",
                         "abs_reduce_fusion"]
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) == 10
    assert len(b["idle_gaps"]) == 10
    assert all(isinstance(n, str) and 0 < s < 0.01
               for n, s in b["idle_gaps"])
