"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Each TPU chip is one plane named ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per operation run on the chip, with start and duration in
nanoseconds, named by the operation's HLO text (``%name = shape op(...)``).
Control flow nests: a ``while`` event spans the operations of its body, so
each operation is charged its self time, its duration less the nested
operations'. Busy time is the union of the intervals; the window is the
span of the host's ``bench_unit`` annotations (one per timed unit) that the
profiler recorded. Idle gaps are labelled by the host event that covers the
gap's middle, the innermost one first.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import Counter

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
UNIT_EVENT = "bench_unit"


@dataclasses.dataclass
class Op:
    name: str             # the HLO instruction's name, without % and suffix
    start: int            # ns
    dur: int              # ns
    chip: int
    self_ns: int = 0      # dur less the time of the ops nested inside


def short_name(hlo: str) -> str:
    """``%cl_fuse_level_pallas.6 = f32[...] custom-call(...)`` →
    ``cl_fuse_level_pallas``."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return name.rstrip("0123456789").rstrip(".") or name


def _self_times(ops: list) -> None:
    """Fill ``self_ns`` of one chip's ops (nesting by containment)."""
    stack: list = []
    for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
        o.self_ns = o.dur
        while stack and stack[-1].start + stack[-1].dur <= o.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= o.dur
        stack.append(o)


@dataclasses.dataclass
class DeviceTrace:
    ops: list                     # every op on the chips used, in the window
    window: tuple                 # (start_ns, end_ns) on the trace clock
    chips: int
    busy_s: float                 # union of op intervals, mean over chips
    window_s: float
    host: list                    # (start, end, name) of host events

    def op_seconds(self, pattern: str) -> float:
        """Device self seconds of the ops whose name ``pattern`` matches
        from its start, summed over chips and divided by their number."""
        rx = re.compile(pattern)
        ns = sum(o.self_ns for o in self.ops if rx.match(o.name))
        return ns / 1e9 / self.chips

    def total_op_seconds(self) -> float:
        return sum(o.self_ns for o in self.ops) / 1e9 / self.chips

    def gaps(self, chip: int = 0) -> list:
        """Idle intervals ``(start, end)`` of one chip inside the window."""
        spans = sorted((o.start, o.start + o.dur) for o in self.ops
                       if o.chip == chip)
        out, cur = [], self.window[0]
        for s, e in spans:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if self.window[1] > cur:
            out.append((cur, self.window[1]))
        return out

    def host_label(self, t: int) -> str:
        """The innermost host event running at ``t``."""
        best = None
        for s, e, name in self.host:
            if s <= t < e and name != UNIT_EVENT and (
                    best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "no host event"

    def breakdown(self, top: int = 10) -> dict:
        per_op = Counter()
        for o in self.ops:
            per_op[o.name] += o.self_ns
        gaps = sorted(self.gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, ns / 1e9 / self.chips]
                           for n, ns in per_op.most_common(top)],
            "idle_gaps": [[self.host_label((s + e) // 2), (e - s) / 1e9]
                          for s, e in gaps],
        }


def _union_ns(spans: list, lo: int, hi: int) -> int:
    total, cur = 0, lo
    for s, e in sorted(spans):
        s, e = max(s, cur), min(e, hi)
        if e > s:
            total += e - s
            cur = e
    return total


def reduce_planes(planes, n_chips: int) -> DeviceTrace:
    """Reduce profiler planes (``ProfileData.planes``) to a DeviceTrace.

    Raises ``ValueError`` where the trace holds no op of a used chip or no
    ``bench_unit`` annotation: a trace with nothing to read is an error,
    never a zero."""
    ops, host, units = [], [], []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Op(short_name(ev.name), int(ev.start_ns),
                              int(ev.duration_ns), chip)
                           for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name == UNIT_EVENT:
                        units.append((s, e))
                    host.append((s, e, ev.name))
    if not units:
        raise ValueError("the trace holds no bench_unit annotation")
    lo = min(s for s, _ in units)
    hi = max(e for _, e in units)
    ops = [o for o in ops if o.start < hi and o.start + o.dur > lo]
    for c in range(n_chips):
        _self_times([o for o in ops if o.chip == c])
    if not ops:
        raise ValueError("the trace holds no device operation in the window")
    busy = sum(_union_ns([(o.start, o.start + o.dur) for o in ops
                          if o.chip == c], lo, hi) for c in range(n_chips))
    host = [h for h in host if h[0] < hi and h[1] > lo]
    return DeviceTrace(ops=ops, window=(lo, hi), chips=n_chips,
                       busy_s=busy / 1e9 / n_chips,
                       window_s=(hi - lo) / 1e9, host=host)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return files[0]


def load(trace_dir: str, n_chips: int) -> DeviceTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(pd.planes, n_chips)
