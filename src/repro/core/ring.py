"""Rotated sparse ring reduce-scatter — the TPU-native mapping of the
paper's multi-hop incremental aggregation (DESIGN §2).

The flattened per-rank gradient is split into K segments; segment j's K-hop
chain starts at rank j and walks the ring, every hop folding that rank's
contribution with the configured node step (Alg 1–5). All K ICI links are
busy every step (a faithful sequential chain would use one), and after the
final shift rank r owns the fully-aggregated segment r — feeding the
ZeRO-sharded flat optimizer directly.

Semantics: per segment, the value path is *identical* to
``chain.run_chain`` on that segment with per-segment budget q_seg
(tested in tests/test_ring_shardmap.py). The Top-Q budget is divided across
segments (block-wise Top-Q — the standard distributed adaptation; DESIGN
§2.5).

Since the device-plan lowering (:mod:`repro.agg.device`) the ring is the
*chain specialization* of the plan-driven kernel:
``rotated_ring_local`` compiles the ring's visiting order to an
:class:`~repro.agg.plan.AggPlan` (every transport offset +1) and runs
:func:`repro.agg.device.run_plan_segments_local`, which emits the same
per-level ``ppermute`` + compact ``(values, indices)`` wire program the
historic hand-written loop did — bit-exact, and generalizing to routed
trees/graphs/schedules. This module keeps the flat layout helpers;
train/step.py assembles the full 3-phase step.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


Array = jax.Array


class RingStats(NamedTuple):
    """Wire accounting, summed over this device's hops (psum later)."""

    bits: Array        # exact paper-§V bits transmitted by this rank
    nnz: Array         # total nonzeros transmitted (float32 to avoid ovf)
    err_sq: Array      # Σ‖e‖² after the round (local sparsification error)


def ring_hops(num_ranks: int) -> int:
    """Wire transmissions per rank per round (K−1 ring + 1 ownership shift)."""
    return num_ranks


def rotated_ring_local(
    cfg,
    flat_local: Array,                # [n] this rank's gradient slice
    ef_local: Array,                  # [n] this rank's EF memory
    weight: Array,                    # scalar D_k
    *,
    axis,                             # mesh axis name or tuple (ring order)
    global_mask_local: Optional[Array] = None,   # [n] TCS mask slice
    participate: Optional[Array] = None,         # scalar 0/1
) -> tuple[Array, Array, RingStats]:
    """Run the rotated ring. Returns (final segment [n//K], new EF [n], stats).

    Must be called inside shard_map with ``axis`` manual. ``n % K == 0``
    (train/step.py pads the flat layout). After return, rank r holds the
    fully-aggregated segment r.

    Chain specialization of the plan-driven kernel: segment rotation is the
    path plan with rotated start ranks, so this lowers the ring's chain
    plan (:func:`repro.agg.device.ring_chain_plan` — every transport offset
    +1) through :func:`repro.agg.device.run_plan_segments_local`, emitting
    one ``ppermute(+1)`` per level exactly as the historic loop did.
    """
    # function-level import: repro.agg.device imports RingStats from here
    from repro.agg.device import ring_chain_plan, run_plan_segments_local

    K = jax.lax.axis_size(axis)
    return run_plan_segments_local(
        cfg, ring_chain_plan(K), flat_local, ef_local, weight, axis=axis,
        global_mask_local=global_mask_local, participate=participate,
        transport="static")


# ---------------------------------------------------------------------------
# Flat layout helpers (pjit-land)
# ---------------------------------------------------------------------------

def padded_flat_dim(tree_or_specs: Any, multiple: int) -> int:
    """Σ leaf sizes, padded up to ``multiple`` (= model×data×pod sizes)."""
    total = sum(int(jnp.size(l)) if isinstance(l, jax.Array)
                else int(functools.reduce(lambda a, b: a * b, l.shape, 1))
                for l in jax.tree.leaves(tree_or_specs))
    return -(-total // multiple) * multiple


def flatten_tree(tree: Any, d_pad: int, dtype=jnp.float32,
                 aligned_axis: Optional[Any] = None) -> Array:
    """Pytree → flat [d_pad] (row-major per leaf, fixed tree order).

    ``aligned_axis`` is reserved for the shard-aligned layout optimization
    (each leaf transposed so its model-sharded dim leads; see EXPERIMENTS
    §Perf) — None gives the naive paper-faithful layout.
    """
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])
    return jnp.pad(flat, (0, d_pad - flat.shape[0]))


def flatten_stacked(tree: Any, d_pad: int, dtype=jnp.float32) -> Array:
    """Pytree with leading stack dim K on every leaf → [K, d_pad]."""
    leaves = jax.tree.leaves(tree)
    k = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(k, -1).astype(dtype) for l in leaves], axis=1)
    return jnp.pad(flat, ((0, 0), (0, d_pad - flat.shape[1])))


def unflatten_tree(template: Any, flat: Array) -> Any:
    """Inverse of flatten_tree (template supplies shapes/dtypes)."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for l in leaves:
        size = int(jnp.size(l)) if isinstance(l, jax.Array) else int(
            functools.reduce(lambda a, b: a * b, l.shape, 1))
        shape = l.shape
        dtype = l.dtype
        out.append(jax.lax.dynamic_slice_in_dim(flat, off, size, 0)
                   .reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def segment_budget(q_total: int, num_segments: int) -> int:
    """Per-segment per-hop budget (block-wise Top-Q).

    Floor division, so summed per-segment budgets never exceed the global
    §V budget: ``num_segments · segment_budget(q, n) ≤ q``. When
    ``q_total < num_segments`` the budget is 0 — those segments transmit
    nothing (the old ``max(1, ·)`` floor silently inflated the global
    budget K-fold in that regime).
    """
    if num_segments <= 0:
        raise ValueError(f"num_segments must be positive, got {num_segments}")
    return max(0, q_total) // num_segments
