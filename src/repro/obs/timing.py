"""Wall-clock phase hooks for benchmarks and host loops.

:class:`PhaseTimer` measures *host* intervals: jitted dispatch, compile,
flush/sync, plan lookup. It deliberately lives outside jit — what it
times on an async backend is the dispatch (plus any blocking the caller
does), which is exactly the honest host-side quantity; per-op device
timings belong to the profiler, not the trace.

Each phase is also a ``jax.profiler.TraceAnnotation`` named
``repro.<track>.<name>``, so a profiler trace shows the host spans on its
own clock beside the device ops (``repro.simulator.flush`` covers the
device→host log fetch). Spans accumulate in memory, on the timer's own
origin; :meth:`PhaseTimer.totals` folds them into the per-phase seconds a
round record carries.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Span:
    name: str
    track: str
    t0_s: float             # from the timer's origin
    dur_s: float = 0.0
    args: Optional[dict] = None


class PhaseTimer:
    """Accumulates named host wall-clock spans.

    >>> timer = PhaseTimer()
    >>> with timer.phase("compile", track="bench"):
    ...     run_once()
    >>> timer.totals()["compile"]
    """

    def __init__(self):
        self.spans: list = []          # of Span
        self._origin = time.perf_counter()

    @contextlib.contextmanager
    def phase(self, name: str, *, track: str = "host",
              args: Optional[dict] = None, keep: bool = True):
        """Time the block as one span, which the block receives. With
        ``keep=False`` the span is not added to :attr:`spans`, so it
        stays out of :meth:`totals`."""
        t0 = time.perf_counter()
        span = Span(name, track, t0 - self._origin, args=args)
        try:
            with TraceAnnotation(f"repro.{track}.{name}"):
                yield span
        finally:
            span.dur_s = time.perf_counter() - t0
            if keep:
                self.spans.append(span)

    def totals(self) -> dict:
        """Summed seconds per phase name."""
        out: dict = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.dur_s
        return out

    def take(self) -> dict:
        """:meth:`totals` then reset — the per-round phases dict."""
        out = self.totals()
        self.spans = []
        return out
