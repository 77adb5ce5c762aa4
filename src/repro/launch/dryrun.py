import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware:
``.lower().compile()`` must succeed on the 16×16 single-pod mesh AND the
2×16×16 multi-pod mesh for every cell; ``memory_analysis()`` proves it
fits; ``cost_analysis()`` + HLO collective parsing feed §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-130m
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results.json
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..",
                                "benchmarks"))

from repro.configs import ARCHS, SHAPES, get_config, shape_cells
from repro.core.algorithms import AggConfig, AggKind
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models import partition
from repro.optim.optimizers import OptConfig
from repro.train.state import TrainConfig
from repro.train.step import (build_prefill_step, build_serve_step,
                              build_train_step, init_state, state_shardings)

import hlo_analysis  # benchmarks/hlo_analysis.py
import roofline as roofline_mod  # benchmarks/roofline.py


def default_train_config(agg_kind: str = "cl_sia",
                         fsdp: bool = False) -> TrainConfig:
    return TrainConfig(agg=AggConfig(kind=AggKind(agg_kind), q=1),
                       opt=OptConfig(name="adamw", lr=3e-4),
                       q_frac=0.01, fsdp_compute=fsdp)


def _mem_dict(ma) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["peak_bytes_estimate"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               agg_kind: str = "cl_sia", fsdp: bool = False,
               verbose: bool = True) -> dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]
    tc = default_train_config(agg_kind, fsdp)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "agg": agg_kind, "status": "ok"}
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            train_step = build_train_step(cfg, tc, mesh)
            state_sds = jax.eval_shape(
                lambda: init_state(cfg, tc, mesh, jax.random.PRNGKey(0)))
            state_sh = state_shardings(cfg, tc, mesh)
            batch_sds = specs_mod.input_specs(cfg, shape_name)
            batch_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                partition.batch_pspecs(cfg, mesh, shape.global_batch),
                is_leaf=lambda x: isinstance(x, P))
            lowered = jax.jit(
                train_step, in_shardings=(state_sh, batch_sh),
            ).lower(state_sds, batch_sds)
        else:
            from repro.models import model as model_mod
            ins = specs_mod.input_specs(cfg, shape_name)
            params_sds = jax.eval_shape(
                lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0)))
            ns = lambda s: NamedSharding(mesh, s)
            p_sh = jax.tree.map(ns, partition.param_pspecs(cfg, mesh),
                                is_leaf=lambda x: isinstance(x, P))
            c_sh = jax.tree.map(ns, partition.cache_pspecs(
                cfg, mesh, shape.global_batch),
                is_leaf=lambda x: isinstance(x, P))
            dpx = partition.batch_axes(mesh)
            dp_total = 1
            for a in dpx:
                dp_total *= mesh.shape[a]
            b_ok = shape.global_batch % dp_total == 0
            if shape.kind == "prefill":
                fn = build_prefill_step(cfg, mesh)
                b_sh = ns(P(dpx if b_ok else None, None))
                args = [params_sds, ins["cache"], ins["tokens"]]
                shardings = [p_sh, c_sh, b_sh]
                if "extra" in ins:
                    e_sh = jax.tree.map(
                        lambda l: ns(P(dpx if b_ok else None,
                                       *([None] * (len(l.shape) - 1)))),
                        ins["extra"])
                    args.append(ins["extra"])
                    shardings.append(e_sh)
                lowered = jax.jit(fn, in_shardings=tuple(shardings)).lower(
                    *args)
            else:  # decode: one token against a seq_len-deep cache
                fn = build_serve_step(cfg, mesh)
                tok_sh = ns(P(dpx if b_ok else None))
                lowered = jax.jit(
                    fn, in_shardings=(p_sh, c_sh, tok_sh, ns(P()))).lower(
                    params_sds, ins["cache"], ins["token"], ins["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware analysis (cost_analysis() visits loop bodies once —
    # wrong by ~num_layers for scanned stacks; see hlo_analysis.py)
    cost = hlo_analysis.analyze(hlo)
    mf = roofline_mod.model_flops_for(cfg, shape, shape.kind)
    rl = roofline_mod.Roofline(
        flops=cost.flops,
        bytes_accessed=cost.hbm_bytes,
        wire_bytes=cost.wire_bytes,
        model_flops=mf,
        chips=chips,
    )
    rec.update({
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": _mem_dict(ma),
        "cost_analysis_raw": {k: float(v) for k, v in list(ca.items())
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed")},
        "collectives": cost.collective_dict(),
        "roofline": rl.as_dict(),
    })
    if verbose:
        mem = rec["memory_analysis"]
        print(f"[{rec['mesh']}] {arch} × {shape_name}: "
              f"peak≈{mem['peak_bytes_estimate']/1e9:.2f} GB/dev, "
              f"flops/dev={rl.flops:.3e}, wire={cost.wire_bytes/1e6:.1f} MB, "
              f"bottleneck={rl.bottleneck}, "
              f"roofline={rl.roofline_fraction:.3f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"  memory_analysis: {mem}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--agg", default="cl_sia")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    existing = {}
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for r in json.load(f):
                existing[(r["arch"], r["shape"], r["mesh"], r.get("agg"))] = r

    cells = []
    if args.all:
        for arch in ARCHS:
            cfg = get_config(arch)
            for shape_name in shape_cells(cfg):
                cells.append((arch, shape_name))
    else:
        arch = args.arch or "mamba2-130m"
        names = [args.shape] if args.shape else shape_cells(get_config(arch))
        cells = [(arch, s) for s in names]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = list(existing.values())
    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            key = (arch, shape_name, "2x16x16" if mp else "16x16", args.agg)
            if key in existing:
                print(f"skip cached {key}")
                continue
            try:
                rec = lower_cell(arch, shape_name, multi_pod=mp,
                                 agg_kind=args.agg, fsdp=args.fsdp)
            except Exception as e:  # a failure here is a bug in our system
                failures += 1
                rec = {"arch": arch, "shape": shape_name,
                       "mesh": "2x16x16" if mp else "16x16",
                       "agg": args.agg, "status": "FAIL",
                       "error": f"{type(e).__name__}: {e}"}
                print(f"FAIL {arch} × {shape_name} ({rec['mesh']}): "
                      f"{rec['error']}")
                traceback.print_exc()
            results.append(rec)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"\n{len(results)} cells, {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
