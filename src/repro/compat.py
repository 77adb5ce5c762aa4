"""Mesh and shard_map helpers shared by the executors, launchers and tests.

Every mesh in this repo has ``Auto`` axes; each shard_map names its manual
axes (``axis_names``) and leaves the rest to the partitioner. Bodies are
written without replication checking, so ``check_vma`` is off throughout.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off.

    ``axis_names`` selects the manual axes; axes not named stay automatic
    inside the body. None makes every mesh axis manual.
    """
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kwargs)
