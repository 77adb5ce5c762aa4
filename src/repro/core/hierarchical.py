"""Hierarchical (pod-aware) sparse incremental aggregation.

The flat production ring treats (pod, data) as one K=32 chain — the
paper's exact topology. DCI links between pods are scarcer than intra-pod
ICI, so the *optimized* schedule aggregates in two stages:

  stage 1: rotated ring over `data` inside each pod (K_d hops on ICI);
  stage 2: rotated ring over `pod` on the stage-1 partial aggregates
           (K_p hops on DCI, payload already CL-sparsified).

Since the nested-plan lowering (:mod:`repro.agg.nested` +
:func:`repro.agg.device.run_nested_segments_local`) this module is the
**chain×chain specialization**: the two-stage schedule compiles to a
:class:`~repro.agg.nested.NestedPlan` (one rotated-ring chain per pod,
then the ring chain over pod partials — :func:`pod_ring_nested`) and runs
through the staged segments kernel, which emits the identical per-level
``ppermute(+1)`` program the historic hand-composed pair of
``rotated_ring_local`` calls did — bit-exact, and generalizing to
arbitrary intra-pod/inter-pod trees. Stage 2's "gradient" is the
pod-local partial aggregate (weight 1), with its own error-feedback
buffer (the pod-edge EF), exactly the paper's multi-hop recursion one
level up. DCI traffic per step drops from K_p·K_d·(segment payload)
(flat ring crosses the pod seam every wrap-around) to
K_p·(segment payload) — the staged closed forms live in
:mod:`repro.core.comm_cost` (``nested_cl_sia_bits``,
``dci_wire_flat_vs_nested``).

Semantics note (documented trade): two-stage CL-SIA applies Top-Q twice
(per-pod then cross-pod) — the composition is *not* bit-identical to the
flat 32-hop chain, but both are instances of the paper's algorithm on a
2-level tree topology; EF at both levels keeps the estimator unbiased in
the same telescoping sense, and mass conservation holds (tested).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax

from repro.core.algorithms import AggConfig
from repro.core.ring import RingStats

Array = jax.Array


@functools.lru_cache(maxsize=None)
def _pod_ring_nested_cached(k_pod: int, k_data: int):
    from repro.agg.nested import pod_ring_nested
    return pod_ring_nested(k_pod, k_data)


class HierStats(NamedTuple):
    intra: RingStats          # ICI (data-axis) accounting
    inter: RingStats          # DCI (pod-axis) accounting


def hierarchical_ring_local(
    cfg: AggConfig,
    flat_local: Array,                # [n] this rank's gradient slice
    ef_local: Array,                  # [n] client-level EF
    pod_ef_local: Array,              # [n // K_data] pod-edge EF
    weight: Array,
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    global_mask_local: Optional[Array] = None,
    participate: Optional[Array] = None,
) -> tuple[Array, Array, Array, HierStats]:
    """Two-stage ring. Must run inside shard_map with both axes manual.

    Returns (final segment [n/(K_d·K_p)], new client EF [n],
    new pod EF [n/K_d], stats per stage). Rank (p, r, m) ends owning
    sub-segment p of segment r of its model column — matching the flat
    master sharding P(("model", "pod", "data")) after the caller's
    reordering (train/step.py uses P(("model",)+dp) with dp=(pod,data);
    the hierarchical variant owns P(("model", "data", "pod"))).

    Thin delegate: the chain×chain :class:`~repro.agg.nested.NestedPlan`
    through :func:`repro.agg.device.run_nested_segments_local` — bit-exact
    to the historic pair of ``rotated_ring_local`` calls (stage 0 is the
    ring's chain plan on ``data``, stage 1 the ring's chain plan on
    ``pod``, both on the static register path).
    """
    from repro.agg.device import run_nested_segments_local

    nested = _pod_ring_nested_cached(jax.lax.axis_size(pod_axis),
                                     jax.lax.axis_size(data_axis))
    seg2, ef_new, (pod_ef_new,), (st1, st2) = run_nested_segments_local(
        cfg, nested, flat_local, ef_local, (pod_ef_local,), weight,
        axes=(data_axis, pod_axis), global_mask_local=global_mask_local,
        participate=participate)
    return seg2, ef_new, pod_ef_new, HierStats(intra=st1, inter=st2)


def dci_bytes_flat_vs_hier(k_pod: int, k_data: int, payload: int) -> tuple:
    """Analytic DCI (pod-seam) wire per round: flat ring vs hierarchical.

    Flat ring over (pod, data): each of the K_p·K_d hops crosses the pod
    seam for the ranks at pod boundaries → K_p seam crossings per step ×
    K_p·K_d steps / (K_p·K_d ranks) = one seam payload per rank-step pair
    on the boundary; total seam traffic = K_p·K_d·payload per round.
    Hierarchical: only stage 2 uses DCI = K_p·payload.
    """
    return k_pod * k_data * payload, k_pod * payload
