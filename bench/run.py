#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload mamba2-130m.round-chain8-thr \
        --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json``. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the window and reports its per-layer
metrics, with ``busy_s``/``window_s`` and a ``breakdown``. The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``checks``); the last lines of standard error give
each compared number beside its limit. Without a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness             # noqa: E402


def measure(cell: harness.Cell, seed: int, seconds: float, trace: bool,
            devices, t_start: float, peaks: dict = None) -> dict:
    """Set up, measure and check one cell; → the result line's object.

    ``peaks`` defaults to the table's entry for the devices' kind."""
    from bench import trace as trace_mod

    kind = devices[0].device_kind
    if peaks is None:
        peaks = harness.load_peaks(kind)
    counter = harness.CompileCounter()
    drv = harness.driver_module(cell.traffic["driver"]).Driver(
        cell.config, cell.traffic, cell.limits, seed, devices, seconds)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace",
                                 cell.name.replace("/", "_"))
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = harness.run_window(drv.step, seconds, counter, trace_dir,
                             float(cell.traffic.get("trace_seconds", 0)))
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    dev_trace = None
    if trace:
        dev_trace = trace_mod.load(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks, attempted, failed, _ = drv.check()
    ctx = harness.Context(cell=cell, shape=drv.shape(), window=win,
                          setup_seconds=setup_s, peaks=peaks,
                          trace=dev_trace)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = harness.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak,
              "compiles_in_window": win.compiles}
    out = {"correct": harness.is_correct(checks) and failed == 0,
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if dev_trace is not None:
        device["busy_s"] = dev_trace.busy_s
        device["window_s"] = dev_trace.window_s
        out["breakdown"] = dev_trace.breakdown()
    out["checks"] = harness.fmt_checks(checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; refusing to run", file=sys.stderr)
        return 3
    harness.setup_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  T_START)
    for c in out["checks"]:
        print(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
