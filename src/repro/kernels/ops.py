"""Backend-dispatching wrappers around the Pallas kernels.

``use_pallas``: "auto" (Pallas compiled on TPU, Pallas-interpret off-TPU
when ``REPRO_PALLAS_INTERPRET=1``, else jnp ref), "always" (interpret mode
off-TPU — used by kernel tests), "never" (pure-jnp ref — used by the
dry-run/roofline path so ``cost_analysis`` sees native HLO), "ref" (jnp
ref kernels like "never", but the fused *structure* — operand-on-the-fly
τ search, fused node steps — stays on; the honest host benchmark of the
fused data flow without Pallas interpret overhead).
"""

from __future__ import annotations

import os
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.level import (chain_accum_level_pallas,
                                 cl_fuse_level_pallas,
                                 count_ge_fused_level_pallas,
                                 count_ge_level_pallas,
                                 hist_topq_level_pallas,
                                 sparsify_ef_level_pallas)

Mode = Literal["auto", "always", "never", "ref"]


def resolve(mode: Mode) -> tuple[bool, bool]:
    """Resolve a dispatch mode → ``(use_pallas, interpret)``.

    Trace-time (Python-level) decision: compiled Pallas on TPU,
    Pallas-interpret off-TPU when forced (``mode="always"`` or
    ``REPRO_PALLAS_INTERPRET=1``), pure-jnp reference otherwise — the
    fused node-step paths in :mod:`repro.core.algorithms` key off this, so
    the host executors stay the bit-exact jnp oracle off-TPU by default.

    ``"ref"`` resolves to the jnp reference kernels too; what distinguishes
    it from ``"never"`` is *structural*: ``fused_node_steps`` treats it as
    fused, so the whole-level fused paths (and the fused-operand τ search)
    run with jnp kernel bodies.
    """
    if mode in ("never", "ref"):
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    if mode == "always":
        return True, not on_tpu
    # auto
    if on_tpu:
        return True, False
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True, True
    return False, False


# ---------------------------------------------------------------------------
# Batched W-lane level variants (the (L, W) schedule hot path)
# ---------------------------------------------------------------------------

def sparsify_ef_level(g, e, mask_in, weight, tau, valid, *,
                      with_err: bool = False, mode: Mode = "auto"):
    """Batched fused EF+sparsify over a level's W lanes ([W, d] inputs).

    ``with_err=True`` appends the in-kernel pinned-order ‖e'‖² ([W] f32) —
    the ``err_sq_mode="kernel"`` path; both backends use the identical
    pairwise-tree fold.
    """
    use, interp = resolve(mode)
    if use:
        return sparsify_ef_level_pallas(g, e, mask_in, jnp.asarray(weight),
                                        jnp.asarray(tau),
                                        jnp.asarray(valid),
                                        with_err=with_err,
                                        interpret=interp)
    return ref.ref_sparsify_ef_level(g, e, mask_in, jnp.asarray(weight),
                                     jnp.asarray(tau), jnp.asarray(valid),
                                     with_err=with_err)


def chain_accum_level(gamma_in, gbar, valid, gmask=None, *,
                      gmask_cohorts: int = 0, mode: Mode = "auto"):
    """Batched IA combine with fused (total, off-global-mask) counts.

    ``gmask_cohorts=B`` marks a cohort-shared [B, d] gmask for lanes laid
    out cohort-major (the multi-tenant batched round path).
    """
    use, interp = resolve(mode)
    if use:
        return chain_accum_level_pallas(gamma_in, gbar, jnp.asarray(valid),
                                        gmask, gmask_cohorts=gmask_cohorts,
                                        interpret=interp)
    return ref.ref_chain_accum_level(gamma_in, gbar, jnp.asarray(valid),
                                     gmask, gmask_cohorts=gmask_cohorts)


def cl_fuse_level(g, e, gamma_in, weight, tau, participate, valid,
                  gmask=None, mask_in=None, *, gmask_cohorts: int = 0,
                  with_err: bool = False, mode: Mode = "auto"):
    """Batched complete CL node step (Algs 3/5, stragglers included).

    ``with_err=True`` appends the in-kernel pinned-order ‖e'‖² ([W] f32).
    """
    use, interp = resolve(mode)
    if use:
        return cl_fuse_level_pallas(g, e, gamma_in, jnp.asarray(weight),
                                    jnp.asarray(tau),
                                    jnp.asarray(participate),
                                    jnp.asarray(valid), gmask, mask_in,
                                    gmask_cohorts=gmask_cohorts,
                                    with_err=with_err, interpret=interp)
    return ref.ref_cl_fuse_level(g, e, gamma_in, jnp.asarray(weight),
                                 jnp.asarray(tau), jnp.asarray(participate),
                                 jnp.asarray(valid), gmask, mask_in,
                                 gmask_cohorts=gmask_cohorts,
                                 with_err=with_err)


def count_ge_level(x: jax.Array, taus: jax.Array, *, mode: Mode = "auto"):
    """Per-lane candidate-threshold counts ([W, d] × [W, B] → [W, B])."""
    use, interp = resolve(mode)
    if use:
        return count_ge_level_pallas(x, taus, interpret=interp)
    return ref.ref_count_ge_level(x, taus)


# ---------------------------------------------------------------------------
# Fused-operand τ search (no materialized bisection operand)
# ---------------------------------------------------------------------------

def count_ge_fused_level(g, e, gamma_in, weight, participate, taus,
                         gmask=None, *, include_gamma: bool = False,
                         gmask_cohorts: int = 0, mode: Mode = "auto"):
    """Per-lane candidate counts of the fused bisection operand.

    Full operand family ``(1−m)·(p·(w·g + e) + γ_in)`` with the γ/mask
    factors dropped per flags; [W, d] inputs, taus [W, B] → [W, B] i32.
    """
    use, interp = resolve(mode)
    if use:
        return count_ge_fused_level_pallas(
            g, e, gamma_in, jnp.asarray(weight), jnp.asarray(participate),
            taus, gmask, include_gamma=include_gamma,
            gmask_cohorts=gmask_cohorts, interpret=interp)
    return ref.ref_count_ge_fused_level(
        g, e, gamma_in, jnp.asarray(weight), jnp.asarray(participate),
        taus, gmask, include_gamma=include_gamma,
        gmask_cohorts=gmask_cohorts)


def hist_topq_level(g, e, gamma_in, weight, participate, tables, gmask=None,
                    *, include_gamma: bool = False, gmask_cohorts: int = 0,
                    mode: Mode = "auto"):
    """One-pass joint digit histogram of the fused operand (tau_impl="hist").

    ``tables`` per ``repro.core.sparsify._hist_tables``; returns
    ``(D2 [W, b+1, b+1] i32, F [W, b+1] i32)``.
    """
    use, interp = resolve(mode)
    if use:
        return hist_topq_level_pallas(
            g, e, gamma_in, jnp.asarray(weight), jnp.asarray(participate),
            tables, gmask, include_gamma=include_gamma,
            gmask_cohorts=gmask_cohorts, interpret=interp)
    return ref.ref_hist_topq_level(
        g, e, gamma_in, jnp.asarray(weight), jnp.asarray(participate),
        tables, gmask, include_gamma=include_gamma,
        gmask_cohorts=gmask_cohorts)
