"""The benchmark's general machinery: loading a cell by name, the chip check,
JAX's compile cache, the measured window, and the result line.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own (``bench/configs``, ``bench/traffic``, ``bench/metrics``,
``bench/limits``); this module finds those files by the names that
``BENCHMARK.json`` gives and edits none of them.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import time
from typing import Any, Callable, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names, read."""

    name: str
    chips: int
    config_name: str
    config: dict          # bench/configs/<config>.json
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json
    end_to_end: list      # the BENCHMARK.json metric entries this cell reports
    per_layer: list


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT, spec: Optional[dict] = None
              ) -> Cell:
    """The cell ``name`` of ``spec`` (by default ``<root>/BENCHMARK.json``)
    with its files.

    Raises ``FileNotFoundError`` where the benchmark or a file it names is
    missing, and ``KeyError`` for a cell it does not list."""
    if spec is None:
        spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_read_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def held_spec(name: str, root: str = ROOT) -> dict:
    """``BENCHMARK.json`` with the entries of ``bench/held/<name>.json``
    added: a cell kept out of the benchmark until a fault of the program
    is mended (``PERF.md``), loadable for its tests and readings."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    held = _read_json(os.path.join(BENCH, "held", name + ".json"))
    for key, entries in held.items():
        spec[key] = spec[key] + entries
    return spec


def load_peaks(device_kind: str, path: Optional[str] = None) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    ``bench/peaks.json`` is an error, never a default."""
    table = _read_json(path or os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def driver_module(name: str):
    """``bench/drivers/<name>.py``: the general generator of one kind of
    traffic (aggregation rounds, train steps, simulator rounds)."""
    return importlib.import_module(f"bench.drivers.{name}")


def metric_reader(name: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    """The TPU devices of this process; ``NoChip`` without ``n`` of them."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:            # no backend at all
        raise NoChip(f"JAX found no devices: {e}") from e
    tpus = [d for d in devs if d.platform == "tpu"]
    if not tpus:
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(tpus) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(tpus)}")
    return tpus[:n]


def setup_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``, or
    at the fixed ``<checkout>/.jax_cache``; small programs are cached too,
    so only the first run of a cell in a checkout compiles."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compiles. JAX reports a persistent-cache hit as a
    backend compile too, with a cache retrieval beside it: a hit is not
    counted."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return self.compiles - self.hits

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.compiles += 1
        elif event == self.CACHE_HIT:
            self.hits += 1


@dataclasses.dataclass
class Window:
    """What the measured window did."""

    seconds: float = 0.0              # wall time of the whole window
    units: int = 0                    # rounds or steps completed
    compiles: int = 0                 # backend compiles inside the window
    trace_units: int = 0              # units inside the traced span


def run_window(step: Callable[[], int], seconds: float,
               counter: CompileCounter, trace_dir: Optional[str] = None,
               trace_seconds: float = 0.0) -> Window:
    """Call ``step`` until ``seconds`` have passed; each call ends with its
    work complete on the device and returns the units it completed.

    With ``trace_dir`` the profiler records the first ``trace_seconds`` of
    the window (the whole window when 0)."""
    import jax
    win = Window()
    c0 = counter.count
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    now = t0
    while now - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench_unit"):
            n = step()
        now = time.perf_counter()
        win.units += n
        if tracing:
            win.trace_units += n
            if trace_seconds and now - t0 >= trace_seconds:
                jax.profiler.stop_trace()
                tracing = False
    win.seconds = now - t0
    if tracing:
        jax.profiler.stop_trace()
    win.compiles = counter.count - c0
    return win


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    shape: dict                       # the driver's sizes of one unit
    window: Window
    setup_seconds: float
    peaks: dict
    trace: Any = None                 # bench.trace.DeviceTrace or None


def fmt_checks(checks: list) -> list:
    """``[(name, value, limit), ...]`` as the result line's last key."""
    return [{"name": n, "value": float(v), "limit": float(lim)}
            for n, v, lim in checks]


def is_correct(checks: list) -> bool:
    return bool(checks) and all(v <= lim for _, v, lim in checks)
