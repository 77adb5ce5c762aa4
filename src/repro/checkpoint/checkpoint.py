"""Fault-tolerant checkpointing: atomic, sharded-friendly, keep-N GC.

Design (restart-anywhere posture, DESIGN §6):

* a checkpoint is a directory ``step_<n>/`` holding one ``.npz`` per
  top-level TrainState field plus a JSON manifest (tree structure, shapes,
  dtypes, step);
* writes go to ``step_<n>.tmp/`` then ``os.replace`` → readers never see a
  partial checkpoint (atomicity on POSIX rename);
* ``keep_n`` oldest checkpoints are garbage-collected after a successful
  commit (never before);
* error-feedback / TCS state are ordinary fields — they ride along, which
  is the point (the paper's convergence depends on them).

On a real multi-host pod each host writes only its addressable shards and
the manifest records the global shape; in this single-process container we
write full arrays but keep the same layout, so the format carries over.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


_NP_SAVABLE = {"float64", "float32", "float16", "int64", "int32", "int16",
               "int8", "uint8", "uint16", "uint32", "uint64", "bool"}


def _savable(arr: np.ndarray) -> np.ndarray:
    """npz can't serialize ml_dtypes (bfloat16/f8); upcast losslessly to
    f32 — restore() casts back to the template's dtype."""
    if arr.dtype.name in _NP_SAVABLE:
        return arr
    return arr.astype(np.float32)


def _flatten_with_paths(tree: Any):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def save(ckpt_dir: str, step: int, state: Any, *, keep_n: int = 3) -> str:
    """Atomically write ``state`` under ``ckpt_dir/step_<step>``."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    paths, leaves, _ = _flatten_with_paths(state)
    arrays = {f"a{i}": _savable(np.asarray(l)) for i, l in enumerate(leaves)}
    np.savez(os.path.join(tmp, "leaves.npz"), **arrays)
    manifest = {
        "step": step,
        "paths": paths,
        "shapes": [list(np.shape(a)) for a in arrays.values()],
        "dtypes": [str(np.asarray(a).dtype) for a in arrays.values()],
        "num_leaves": len(leaves),
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)                      # atomic commit

    # GC after commit
    ckpts = sorted(d for d in os.listdir(ckpt_dir)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    for old in ckpts[:-keep_n] if keep_n > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, old))
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_") and not d.endswith(".tmp")
             and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json"))]
    return max(steps) if steps else None


def restore(ckpt_dir: str, template: Any, *, step: Optional[int] = None,
            shardings: Any = None) -> Any:
    """Restore into the structure of ``template`` (validates leaf count).

    ``shardings``: optional NamedSharding pytree — leaves are device_put
    accordingly (restart onto a different mesh layout = elastic restore).
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(d, "leaves.npz"))
    leaves = [data[f"a{i}"] for i in range(manifest["num_leaves"])]

    t_leaves, treedef = jax.tree.flatten(template)
    if len(t_leaves) != len(leaves):
        raise ValueError(
            f"checkpoint has {len(leaves)} leaves, template expects "
            f"{len(t_leaves)} — incompatible TrainConfig?")
    out = []
    s_leaves = (jax.tree.leaves(shardings) if shardings is not None
                else [None] * len(leaves))
    for tl, arr, sh in zip(t_leaves, leaves, s_leaves):
        a = jnp.asarray(arr, dtype=tl.dtype)
        if sh is not None:
            a = jax.device_put(a, sh)
        out.append(a)
    return jax.tree.unflatten(treedef, out)
