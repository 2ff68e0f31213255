"""One run of one cell: set up, serve for a fixed window, check, report.

What is served is the configuration's path (``"path"``, by default
``compress``): a module ``bench/paths/<path>.py`` whose class ``Path``
the run drives in this order.

- ``Path(cfg, mix, seed=...)``: set-up; the inputs drawn from the seed,
  the system under test (``.server``, handed to ``before_window``) and
  the warm-up of every program the window runs.
- ``.serve(seconds=, trace=, t_start=, logdir=)``: the window, as a
  :class:`Served`.  With ``trace`` the path hands the host spans it
  recorded to :func:`read_trace` and fills a :class:`LayerInput`.
- ``.release()``: after the device's peak memory is read, take what the
  program served and drop its state.
- ``.check()``: ``{name: {"value", "limit"}}`` under the path's own
  ``LIMITS``.

Everything here is shared by every path: the benchmark's files, the
compile counter, the reduction of the profiler's trace, the readers of
per-layer metrics and the assembly of the result.  The harness runs on
whatever JAX finds; ``run.py`` refuses to start it without the chips a
cell asks for.  Tests drive it on the CPU at toy sizes through
:func:`run`.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import resource
import sys
import time
import types
from typing import Any, Callable, Dict, List, Mapping, NamedTuple, Optional, Tuple

import jax

from bench import tracing

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DEFAULT_PATH = "compress"
PATHS = ("bench.paths",)  # packages that hold served paths, searched in order


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


# -- the benchmark's files ------------------------------------------------------


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def spec() -> Dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r}; have {[w['name'] for w in bench['workloads']]}"
    )


def config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no config {name!r}")


def traffic(name: str) -> Dict:
    return load_json(os.path.join(BENCH, "traffic", name + ".json"))


def _listed(metric: Dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def metrics_of(bench: Dict, cell: str) -> Tuple[List[Dict], List[Dict]]:
    """The end-to-end and the per-layer metrics a cell reports."""
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _listed(m, cell, names)]
    return e2e, layer


def reader(name: str) -> Callable:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or for a
    metric split by regime (``<quantity>.<regime>``) the quantity's
    ``metrics/<quantity>.py``."""
    base = os.path.join(BENCH, "metrics")
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(base, stem + ".py")
        if os.path.exists(path):
            mod_spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path
            )
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise KeyError(f"no reader for metric {name!r} under {base}")


def peaks(device_kind: str) -> Dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table['devices'])})"
        )
    return table["devices"][device_kind]


def _package_dir(package: str) -> str:
    return os.path.dirname(importlib.util.find_spec(package).origin)


def path_names() -> List[str]:
    """Every served path there is, by the name a configuration gives."""
    return sorted({
        f[:-3] for p in PATHS for f in os.listdir(_package_dir(p))
        if f.endswith(".py") and not f.startswith("_")
    })


def path_module(name: str) -> types.ModuleType:
    """The module of the served path ``name``: ``<package>/<name>.py`` in
    the first package of :data:`PATHS` that has it."""
    for p in PATHS:
        if os.path.exists(os.path.join(_package_dir(p), name + ".py")):
            return importlib.import_module(f"{p}.{name}")
    raise KeyError(f"no served path {name!r}; there are {path_names()}")


# -- clocks and records ------------------------------------------------------------


class CompileCounter:
    """Times at which JAX compiled (or loaded a cached executable)."""

    def __init__(self):
        self.times: List[float] = []

    def _listen(self, event: str, duration: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.times.append(time.monotonic())

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


class LayerInput(NamedTuple):
    """What a per-layer reader reads: host spans from the window before
    the traced slice, device operations and chunks from inside it."""

    trace: tracing.Trace  # the traced slice
    ticks: List[Dict[str, Any]]  # FlightRecorder ticks (spans per tick) before it
    decode_s: List[float]  # host seconds per decoded data frame before it
    stepped: List[Any]  # the compressor's chunks read back inside it
    cfg: Dict
    peaks: Dict
    records: Mapping[str, Any] = types.MappingProxyType({})  # a path's own, by name


class Traced(NamedTuple):
    """The profiler's trace of a window's last slice."""

    trace: tracing.Trace
    t0: float  # the slice's start and end on the host's monotonic clock
    t1: float
    busy_s: float
    breakdown: Dict[str, Any]


class Served(NamedTuple):
    """What a path's window gave."""

    window: Tuple[float, float]
    attempted: int
    failed: int
    e2e: Dict[str, float]  # by the benchmark's metric names
    latencies: List[Tuple[float, float]]  # (due, seconds) per request due in the window
    traced: Optional[Traced]  # with --trace 1
    layer_input: Optional[LayerInput]  # with --trace 1


def read_trace(logdir: str, wall_minus_mono: int,
               host_spans: List[Tuple[str, float, float]]) -> Traced:
    """Load the traced slice, put it on the host's clock, and name its
    device time and its longest idle gaps by the host spans (name, start,
    end on the host's monotonic clock) that a path recorded."""
    tr = tracing.load(logdir)
    offset = tracing.host_offset(tr, wall_minus_mono)
    say(
        f"trace: {tr.window_s:.3f} s; device planes "
        + ", ".join(f"{p} ({len(ev)} ops)" for p, ev in sorted(tr.device.items()))
        + f"; {len(tr.host)} host events"
    )
    busy = tracing.busy_seconds(tr)
    if not busy:
        raise RuntimeError("the traced slice holds no device operation")
    spans = [(name, a + offset, b + offset) for name, a, b in host_spans]
    return Traced(tr, -offset, tr.window_s - offset, busy, {
        "device_ops": tracing.top_ops(tr),
        "idle_gaps": tracing.idle_gaps(tr, spans),
    })


# -- one run ------------------------------------------------------------------------------


class Outcome(NamedTuple):
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict[str, Any]]
    device: Dict[str, Any]
    breakdown: Optional[Dict[str, Any]]
    checks: Dict[str, Dict[str, Any]]
    latencies: List[Tuple[float, float]]  # (due, seconds) per request due in the window


def run(
    cfg: Dict,
    mix: Dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    e2e: List[Dict],
    per_layer: List[Dict],
    t_start: float,
    logdir: str,
    before_window: Optional[Callable[[Any], None]] = None,
) -> Outcome:
    """Serve one cell once on its configuration's path and check what it
    served.

    ``before_window`` is called with the warmed system under test before
    traffic starts: tests break the served path there.
    """
    path = path_module(cfg.get("path", DEFAULT_PATH))
    with CompileCounter() as compiles:
        cell = path.Path(cfg, mix, seed=seed)
        if before_window is not None:
            before_window(cell.server)
        served = cell.serve(seconds=seconds, trace=trace, t_start=t_start, logdir=logdir)
    say(f"compiles inside the window: {compiles.between(*served.window)}")
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": _peak_bytes(),
    }
    cell.release()
    gc.collect()
    say(f"host peak RSS: {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6:.2f} GB")

    checks = cell.check()
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        device.update(busy_s=served.traced.busy_s, window_s=served.traced.trace.window_s)
        for m in per_layer:
            value = reader(m["name"])(served.layer_input)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = served.traced.breakdown
    else:
        for m in e2e:
            if m["name"] in served.e2e:
                metrics[m["name"]] = {"value": served.e2e[m["name"]], "unit": m["unit"]}
    return Outcome(
        all(c["value"] <= c["limit"] for c in checks.values()),
        served.attempted, served.failed, metrics, device, breakdown,
        checks, served.latencies,
    )


def _peak_bytes() -> int:
    stats_ = jax.devices()[0].memory_stats() or {}
    return int(stats_.get("peak_bytes_in_use", 0))
