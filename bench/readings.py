#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, in one process.

    python bench/readings.py --workload mamba2-130m.round-chain8-thr \
        --seeds 101-112 --variants control --variant-seeds 3 --seconds 3

For each seed it sets the cell up, runs a short window, and reads each
compared number off the program; on the first ``--variant-seeds`` seeds it
also reads them off each stand-in the driver offers in the program's place
(``control``: the reference in the next lower precision; ``half_batch``:
the reference fed half of each batch). The limits of ``bench/limits`` are
set from these readings: above the program's largest, below the control's
smallest. Prints one JSON object per seed and a summary; needs the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness      # noqa: E402


def seed_list(spec: str) -> list:
    """``"311,308,401-410"`` → [311, 308, 401, ..., 410]."""
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control")
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    try:
        cell = harness.load_cell(args.workload)
    except KeyError:                   # a cell held out of the benchmark
        cell = harness.load_cell(args.workload,
                                 spec=harness.held_spec(args.workload))
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/readings.py: {e}; refusing to run", file=sys.stderr)
        return 3
    harness.setup_compile_cache()
    counter = harness.CompileCounter()
    variants = tuple(v for v in args.variants.split(",") if v)
    mod = harness.driver_module(cell.traffic["driver"])
    rows = []
    for i, seed in enumerate(seed_list(args.seeds)):
        t0 = time.perf_counter()
        drv = mod.Driver(cell.config, cell.traffic, cell.limits, seed,
                         devices, args.seconds)
        drv.setup()
        harness.run_window(drv.step, args.seconds, counter)
        want = variants if i < args.variant_seeds else ()
        _, attempted, _, readings = drv.check(want)
        row = {"seed": seed, "attempted": attempted, **readings,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del drv
        gc.collect()

    names = list(rows[0]["program"])
    summary = {"program_max": {n: max(r["program"][n] for r in rows)
                               for n in names}}
    for v in variants:
        got = [r[v] for r in rows if v in r]
        if got:
            summary[v + "_min"] = {n: min(g[n] for g in got) for n in names}
    summary["seeds"] = len(rows)
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
