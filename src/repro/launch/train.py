"""Training CLI driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
        --steps 50 --agg cl_sia --ckpt-dir /tmp/ckpt

Runs on whatever devices exist (1 CPU here; the production mesh shape is
taken from --mesh, padded down to the available device count). Resumes from
the newest checkpoint in --ckpt-dir if present.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCHS, get_config
from repro.core.algorithms import AggConfig, AggKind
from repro.data.synthetic import lm_batch, make_bigram_lm
from repro.launch.mesh import make_agg_plan, make_mesh
from repro.models.stubs import audio_stub_embeds, vision_stub_embeds
from repro.optim.optimizers import OptConfig
from repro.runtime.fault import StragglerModel
from repro.train.state import TrainConfig, TrainState
from repro.train.step import (build_train_step, dp_size, init_state,
                              state_shardings)


def _topology(name: str, k: int):
    """CLI topology name → something ``compile_plan`` accepts (or None)."""
    if name == "hierarchical":
        # two-stage pod/ICI nested plan (needs a pod axis: --mesh PxDxM)
        return "hierarchical"
    if name != "ring" and k <= 2:
        print(f"topology {name!r} needs >2 DP clients (have {k}); "
              f"falling back to the rotated ring")
        name = "ring"
    if name == "ring":
        return None                      # the rotated ring (paper chain)
    if name == "chain":
        return k                         # identity chain, PS at client 0
    from repro.topo import graph as tg
    from repro.topo.tree import star_tree
    if name == "star":
        return star_tree(k)
    rows = max(d for d in range(1, int(k ** 0.5) + 1) if k % d == 0)
    if name == "grid":
        if rows == 1:                    # prime K: a 1×K grid is a path
            print(f"grid needs composite K (have {k}); the 1x{k} grid "
                  f"degenerates to the chain")
        return tg.grid_graph(rows, k // rows)
    if name == "walker-delta":
        if rows == 1:                    # prime K: no orbital planes
            print(f"walker-delta needs composite K (have {k}); using the "
                  f"star topology instead")
            return star_tree(k)
        return tg.walker_delta(rows, k // rows)
    raise ValueError(f"unknown topology {name!r}")


def main(argv=None) -> dict:
    """Run the CLI with ``argv`` (``sys.argv[1:]`` when None); returns the
    last step's metrics."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--agg", default="cl_sia",
                    choices=[k.value for k in AggKind if k != AggKind.ROUTING])
    ap.add_argument("--q-frac", type=float, default=0.01)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--opt", default="adamw")
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2 → (data=2, model=2); default all-data")
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "chain", "star", "grid",
                             "walker-delta", "hierarchical"],
                    help="aggregation route over the K_dp clients (device-"
                         "plan lowering; 'ring' = the rotated ring; "
                         "'hierarchical' = the two-stage pod/ICI nested "
                         "plan, needs --mesh PxDxM)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--straggle-p", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (n_dev, 1)
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    mesh = make_mesh(shape, axes)
    cfg = get_config(args.arch, smoke=args.smoke)
    tc = TrainConfig(
        agg=AggConfig(kind=AggKind(args.agg), q=1),
        opt=OptConfig(name=args.opt, lr=args.lr),
        q_frac=args.q_frac,
        agg_dtype="float32" if args.smoke else "bfloat16",
        ef_dtype="float32" if args.smoke else "bfloat16",
    )

    agg_plan = make_agg_plan(mesh, _topology(args.topology, dp_size(mesh)))

    with jax.set_mesh(mesh):
        state = init_state(cfg, tc, mesh, jax.random.PRNGKey(args.seed),
                           topology=agg_plan)
        shardings = state_shardings(cfg, tc, mesh, topology=agg_plan)
        state = jax.device_put(state, shardings)
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            template = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
            state = ckpt.restore(args.ckpt_dir, template,
                                 shardings=shardings)
            print(f"resumed from step {int(state.step)}")
        step_fn = jax.jit(build_train_step(cfg, tc, mesh,
                                           topology=agg_plan))

        lm = make_bigram_lm(jax.random.PRNGKey(7), cfg.vocab_size)
        sm = StragglerModel(p_straggle=args.straggle_p)
        k_dp = dp_size(mesh)
        key = jax.random.PRNGKey(args.seed + 1)
        t0 = time.time()
        metrics = {}
        for i in range(args.steps):
            key, kb, ks = jax.random.split(key, 3)
            batch = lm_batch(lm, kb, args.batch, args.seq)
            if cfg.frontend == "vision":
                fe, m = vision_stub_embeds(cfg, kb, args.batch, args.seq, 8)
                batch |= {"frontend_embeds": fe, "frontend_mask": m}
            elif cfg.frontend == "audio":
                batch |= {"frontend_embeds":
                          audio_stub_embeds(cfg, kb, args.batch, args.seq)}
            if args.straggle_p > 0:
                batch["participate"] = sm.sample(ks, k_dp)
            state, metrics = step_fn(state, batch)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {int(state.step):4d} "
                      f"loss {float(metrics['loss']):.4f} "
                      f"agg_bits {float(metrics['agg_bits']):.3e} "
                      f"({time.time()-t0:.1f}s)")
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, int(state.step), state)
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, int(state.step), state)
            print(f"checkpointed step {int(state.step)} → {args.ckpt_dir}")
    return metrics


if __name__ == "__main__":
    main()
