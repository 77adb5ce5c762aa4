"""TrainState: everything that must survive a restart (checkpointed whole).

The aggregation state (per-client error feedback, TCS previous params) is
*training state*, exactly like optimizer moments — losing it silently
changes convergence (the paper's EF banks untransmitted gradient mass).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.algorithms import AggConfig, AggKind
from repro.optim.optimizers import FlatOptState, OptConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Distributed-training configuration (aggregation + optimizer)."""

    agg: AggConfig = AggConfig(kind=AggKind.CL_SIA, q=1)
    opt: OptConfig = OptConfig()
    q_frac: float = 0.01            # global Q = q_frac · D_pad per round
    agg_dtype: str = "bfloat16"     # storage dtype of G / EF buffers
    ef_dtype: str = "bfloat16"
    lr_warmup: int = 100
    lr_decay_steps: int = 10_000
    # FSDP-style compute: shard the local batch over `model` too (weights
    # stay model-sharded and are gathered per layer) instead of TP
    # activation all-reduces. Wins when 2·activations·layers ≫ params
    # (EXPERIMENTS §Perf pair A). SSM/hybrid archs do this regardless.
    fsdp_compute: bool = False
    # EF as one flat [K_dp·D_pad] vector, each (client, model column)
    # block contiguous, instead of [K_dp, D_pad]: a [1, D] bfloat16 block
    # tiles two rows per TPU tile (twice its bytes) and the ring reads it
    # through a relayout copy in float32; the flat block needs neither.
    # Flat, single-tenant topologies.
    ef_flat: bool = False

    def needs_tcs(self) -> bool:
        return self.agg.kind in (AggKind.TC_SIA, AggKind.CL_TC_SIA)


class TrainState(NamedTuple):
    step: Array                     # int32 scalar
    params: Any                     # working pytree (model dtype, TP-sharded)
    master: Array                   # [D_pad] fp32, fully sharded (ZeRO)
    opt: FlatOptState               # flat, sharded like master
    ef: Array                       # [K_dp, D_pad] per-client error feedback
                                    # ([K_dp·D_pad] with TrainConfig.ef_flat)
    tcs_prev: Optional[Any]         # params-shaped pytree (TC algorithms)
    # upper-tier EF of a nested (staged) aggregation topology: one
    # [K_dp, D_pad // prod(K_0..K_{s-1})] array per stage ≥ 1 (rank
    # (dp, model) holds its stage-s EF slice) — None for flat topologies,
    # keeping the historic pytree structure and checkpoints unchanged
    stage_ef: Optional[tuple] = None


def abstract_like(tree: Any) -> Any:
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
