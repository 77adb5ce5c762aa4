#!/usr/bin/env python3
"""Device time per program scope: a profiler trace joined with the timed
program's compiled text.

The program names its layers with ``jax.named_scope`` (``repro.obs.scopes``);
each scope lands in the ``op_name`` metadata of the compiled module's
instructions. A chip trace's ``XLA Ops`` events carry no scope, but each is
named by its HLO instruction (``%pad_reduce_fusion.12 = ...``), so the
instruction name joins an op with its scopes. Only the ops inside the timed
program's ``XLA Modules`` events join; an op of any other module, or one
whose instruction the text does not hold, is unscoped, so a failed join
shows as a large unscoped share and never as time booked to a layer. An op
the compiler made (no op_name of its own) takes a neighbour's scope, and
its time is reported apart as ``inherited_ms``.

    # on the chip: a traced window and the timed program's text
    python bench/scope_trace.py record --seed 7 --seconds 5 --out DIR \
        --workload mamba2-130m.round-chain8-thr
    # ms per unit of each scope
    python bench/scope_trace.py read DIR
    # are two recorded programs equal but for metadata?
    python bench/scope_trace.py same DIR_A DIR_B
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import glob
import gzip
import json
import os
import re
import sys
import time
from collections import defaultdict
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import trace as trace_mod  # noqa: E402

MODULES_LINE = "XLA Modules"
TEXT_FILE = "module.txt.gz"


def module_name(hlo_text: str) -> str:
    """``HloModule jit__unknown, ...`` → ``jit__unknown``."""
    head = hlo_text.lstrip().split("\n", 1)[0]
    if not head.startswith("HloModule "):
        raise ValueError("not an HLO module's text")
    return head.split()[1].rstrip(",")


def instruction_name(event_name: str) -> str:
    """``%cl_fuse_level_pallas.6 = f32[...] custom-call(...)`` →
    ``cl_fuse_level_pallas.6``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


class ScopedOp(NamedTuple):
    name: str                     # its instruction, e.g. ``copy.3``
    self_ns: int
    path: tuple                   # its known scopes, outermost first
    inherited: bool               # ``path`` is a neighbour's (no op_name)


@dataclasses.dataclass
class ScopedTrace:
    trace: object                 # bench.trace.DeviceTrace of the window
    ops: list                     # of ScopedOp
    units: int                    # bench_unit annotations in the window

    def seconds(self, scope=None, inclusive: bool = False,
                inherited_only: bool = False) -> float:
        """Device self seconds per chip of the ops whose innermost scope is
        ``scope`` (with ``inclusive``, of those under it at any depth);
        ``scope=None`` selects the unscoped ops. ``inherited_only`` keeps
        only the ops whose scope is inherited."""
        def hit(o):
            if inherited_only and not o.inherited:
                return False
            if scope is None:
                return not o.path
            return (scope in o.path if inclusive
                    else o.path[-1:] == (scope,))
        ns = sum(o.self_ns for o in self.ops if hit(o))
        return ns / 1e9 / self.trace.chips

    def partition(self, names, inclusive: bool = False) -> dict:
        """Seconds per chip of each scope in ``names``, and of every other
        op under ``"unscoped"``: a partition of the ops' self time (with
        ``inclusive``, of the ops under none of ``names``)."""
        out = {s: self.seconds(s, inclusive) for s in names}
        total = sum(o.self_ns for o in self.ops) / 1e9 / self.trace.chips
        if inclusive:
            rest = sum(o.self_ns for o in self.ops
                       if not set(o.path) & set(names))
            out["unscoped"] = rest / 1e9 / self.trace.chips
        else:
            out["unscoped"] = total - sum(out.values())
        return out


def join(planes, hlo_text: str, n_chips: int) -> ScopedTrace:
    """Join profiler planes (``ProfileData.planes``) with the compiled text
    of the program timed in their window."""
    from repro.obs import scopes
    planes = list(planes)
    dev = trace_mod.reduce_planes(planes, n_chips)
    lo, hi = dev.window
    name = module_name(hlo_text)
    scoped = {i.name: i for i in scopes.instructions(hlo_text)
              if i.executed}
    ops, runs = [], defaultdict(list)
    for plane in planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= n_chips:
            continue
        chip = int(m.group(1))
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs[chip].extend(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name.split("(", 1)[0] == name)
            elif line.name == trace_mod.OPS_LINE:
                ops.extend(trace_mod.Op(instruction_name(ev.name),
                                        int(ev.start_ns),
                                        int(ev.duration_ns), chip)
                           for ev in line.events)
    ops = [o for o in ops if o.start < hi and o.start + o.dur > lo]
    for c in range(n_chips):
        trace_mod._self_times([o for o in ops if o.chip == c])

    def scoped_op(o) -> ScopedOp:
        inside = any(s <= o.start < e for s, e in runs[o.chip])
        i = scoped.get(o.name) if inside else None
        if i is None:
            return ScopedOp(o.name, o.self_ns, (), False)
        return ScopedOp(o.name, o.self_ns, i.path, i.inherited)

    units = sum(1 for *_, n in dev.host if n == trace_mod.UNIT_EVENT)
    return ScopedTrace(trace=dev, ops=[scoped_op(o) for o in ops],
                       units=units)


def load(directory: str, n_chips: int = 1) -> ScopedTrace:
    """Join the ``.xplane.pb`` under ``directory`` with its module text."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(directory, TEXT_FILE), "rt") as f:
        text = f.read()
    pd = ProfileData.from_file(trace_mod.find_xplane(directory))
    return join(pd.planes, text, n_chips)


_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_KERNEL = re.compile(r'("custom_call_config":\{"body":")([A-Za-z0-9+/=]+)"')
_LOCATION_TABLES = re.compile(r"\nFileNames\n.*?\n(?=%|ENTRY)", re.S)


def _kernel_asm(body: str) -> str:
    """A Mosaic kernel's serialized module, printed without its source
    locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def stripped(hlo_text: str) -> str:
    """An HLO module's text without what names and source lines change:
    the ``metadata`` of each instruction, the tables of source locations,
    and the locations inside each Mosaic kernel's serialized body."""
    text = _LOCATION_TABLES.sub("\n", _METADATA.sub("", hlo_text))
    return _KERNEL.sub(lambda m: m.group(1) + json.dumps(
        _kernel_asm(m.group(2)))[1:-1] + '"', text)


# ---------------------------------------------------------------------------
# Recording a cell's window on the chip
# ---------------------------------------------------------------------------

def program_text(drv) -> str:
    """The optimized HLO text of the program a driver times (an in-memory
    cache hit: the driver's jit has compiled it in set-up).

    The persistent compile cache keys a program without its metadata, so a
    hit there may hold another scoping of the same program; a process with
    that cache on is refused."""
    import jax
    if (jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        raise RuntimeError(
            "program_text with the persistent compile cache on: its text "
            "may carry the scopes of another source")
    kind = type(drv).__module__.rsplit(".", 1)[-1]
    if kind == "round":
        return drv.fn.lower(drv.plan, drv.pool[0], drv.e, drv.w,
                            participate=drv.part).compile().as_text()
    if kind == "train":
        with jax.set_mesh(drv.mesh):
            return drv.fn.lower(drv.state,
                                drv._batch(0)).compile().as_text()
    raise ValueError(f"no program text for the {kind!r} driver")


def record(workload: str, seed: int, seconds: float, out: str) -> dict:
    """Set up the cell's driver, profile ``seconds`` of its window, and
    write the trace and the timed program's text under ``out``. Set-up
    compiles from the source, with the persistent compile cache off."""
    import jax
    from bench import harness
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(workload)
    devices = harness.require_chips(cell.chips)
    counter = harness.CompileCounter()
    drv = harness.driver_module(cell.traffic["driver"]).Driver(
        cell.config, cell.traffic, cell.limits, seed, devices, seconds)
    drv.setup()
    os.makedirs(out, exist_ok=True)
    for f in glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True):
        os.remove(f)
    win = harness.run_window(drv.step, seconds, counter, out)
    t0 = time.perf_counter()
    text = program_text(drv)
    text_s = time.perf_counter() - t0
    with gzip.open(os.path.join(out, TEXT_FILE), "wt") as f:
        f.write(text)
    t0 = time.perf_counter()
    load(out, len(devices))
    join_s = time.perf_counter() - t0
    return {"units": win.units, "compiles_in_window": win.compiles,
            "text_s": text_s, "join_s": join_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    rec = sub.add_parser("record")
    rec.add_argument("--workload", required=True)
    rec.add_argument("--seed", type=int, required=True)
    rec.add_argument("--seconds", type=float, default=5.0)
    rec.add_argument("--out", required=True)
    rd = sub.add_parser("read")
    rd.add_argument("dir")
    rd.add_argument("--chips", type=int, default=1)
    same = sub.add_parser("same")
    same.add_argument("dirs", nargs=2)
    args = ap.parse_args(argv)
    if args.cmd == "same":
        texts = []
        for d in args.dirs:
            with gzip.open(os.path.join(d, TEXT_FILE), "rt") as f:
                texts.append(stripped(f.read()))
        print(json.dumps({"identical": texts[0] == texts[1],
                          "chars": [len(t) for t in texts]}))
        return 0 if texts[0] == texts[1] else 1
    if args.cmd == "record":
        print(json.dumps(record(args.workload, args.seed, args.seconds,
                                args.out)))
        return 0
    from repro.obs import scopes
    st = load(args.dir, args.chips)
    per = 1e3 / max(st.units, 1)
    inner = st.partition(scopes.SCOPES)
    incl = {s: st.seconds(s, inclusive=True) for s in scopes.SCOPES}
    inherited = {s: st.seconds(s, inherited_only=True)
                 for s in scopes.SCOPES}
    print(json.dumps({
        "units": st.units, "busy_ms": st.trace.busy_s * per,
        "innermost_ms": {k: v * per for k, v in inner.items() if v},
        "inclusive_ms": {k: v * per for k, v in incl.items() if v},
        "inherited_ms": {k: v * per for k, v in inherited.items() if v}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
