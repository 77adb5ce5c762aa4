"""Pallas TPU kernels for the aggregation hot path (see DESIGN §3).

Batched W-lane level kernels (one ``pallas_call`` per schedule level,
padding lanes skipped; a single node is W = 1) live in
:mod:`repro.kernels.level` and power the fused node-step paths of
:mod:`repro.core.algorithms`: sparsify_ef (fused EF + sparsify),
chain_accum (fused IA combine), cl_fuse (whole CL node step), and the
Top-Q threshold search's count and histogram kernels.
Dispatch through :mod:`repro.kernels.ops`; oracles in
:mod:`repro.kernels.ref`.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
