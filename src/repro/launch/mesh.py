"""Production mesh construction (function, not constant — importing this
module never touches jax device state). Every axis is ``Auto``
(:func:`repro.compat.make_mesh`).

A mesh also fixes the *aggregation client set*: the combined DP axes
(``pod`` × ``data``) are the K clients of the multi-hop round.
:func:`make_agg_plan` compiles any topology over exactly that client count,
so launchers hand :func:`repro.train.step.build_train_step` an
:class:`~repro.agg.plan.AggPlan` instead of assuming the ring."""

from __future__ import annotations

from typing import Any, Optional

from repro import compat

def dp_clients(mesh) -> int:
    """Number of aggregation clients a mesh provides (pod × data size)."""
    from repro.train.step import dp_size   # the one source of the DP rule
    return dp_size(mesh)


def make_agg_plan(mesh, topology: Any = None, *,
                  pad_to: Optional[tuple] = None, q_budget=None):
    """Compile ``topology`` into an AggPlan sized for ``mesh``'s DP ring.

    ``None`` gives the rotated ring's chain plan (the paper baseline,
    bit-exact to the historic ``rotated_ring_local``); an ``AggTree``,
    chain order, ``ConstellationGraph``, or int K goes through
    :func:`repro.agg.compile_plan` with ``num_clients`` pinned to the mesh.

    Nested (staged) topologies compile to a
    :class:`~repro.agg.nested.NestedPlan` instead: ``"hierarchical"``
    gives the two-stage pod/ICI chain×chain over the mesh's (pod, data)
    axes (``core/hierarchical.py``'s schedule); a ``NestedPlan``, a routed
    :class:`~repro.topo.routing.NestedTopology`, or an explicit stage
    spec goes through :func:`repro.agg.compile_nested`. The train step
    lowers those via ``run_nested_segments_local`` (stage s on dp axis
    S−1−s, minor axis first).
    """
    from repro.agg import compile_nested, compile_plan, pod_ring_nested
    from repro.agg.device import ring_chain_plan, ring_chain_tree
    from repro.agg.nested import NestedPlan

    k = dp_clients(mesh)
    if topology is None:
        # the ring chain even when padded/budgeted — NOT path_tree(k),
        # whose reversed visiting order is a bitwise-different chain
        if pad_to is None and q_budget is None:
            return ring_chain_plan(k)
        topology = ring_chain_tree(k)
    if isinstance(topology, str) and topology == "hierarchical":
        from repro.train.step import dp_axes
        axes = dp_axes(mesh)
        if len(axes) < 2:
            raise ValueError(
                f"'hierarchical' needs two DP axes (pod, data); mesh has "
                f"{axes}")
        k_data = mesh.shape[axes[-1]]
        nested = pod_ring_nested(k // k_data, k_data, q_budget=q_budget)
        return nested if pad_to is None else nested.pad(pad_to)
    if isinstance(topology, NestedPlan) or hasattr(topology,
                                                   "nested_stages"):
        nested = compile_nested(topology, num_clients=k, q_budget=q_budget,
                                pad_to=pad_to)
        if nested.num_clients != k:
            raise ValueError(f"nested topology has {nested.num_clients} "
                             f"clients but the mesh provides {k} DP ranks")
        return nested
    return compile_plan(topology, num_clients=k, pad_to=pad_to,
                        q_budget=q_budget)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary (test-sized) mesh with the same axis conventions."""
    return compat.make_mesh(tuple(shape), tuple(axes))


def make_host_mesh():
    """Single-device mesh (CPU smoke tests / examples)."""
    return compat.make_mesh((1, 1), ("data", "model"))
