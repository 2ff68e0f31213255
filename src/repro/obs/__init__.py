"""repro.obs — the observability subsystem (PR 10).

One layer, three concerns, all host-side (no jax imports anywhere in
this package — recording must never perturb the serving contracts):

  Counter, Gauge, Histogram,
  MetricsRegistry                 (metrics)   typed metrics registry:
                                              labelled cells, snapshot /
                                              merge / JSON / Prometheus
                                              export — the single backing
                                              store behind
                                              ``IngestServer.counters()``,
                                              ``server_counters`` and the
                                              latency recorder
  FlightRecorder, NULL_SPAN,
  TICK_PHASES, EVENT_NAMES,
  self_seconds                     (trace)    per-tick and per-chunk span
                                              tracing into bounded rings;
                                              dumps the last N ticks as
                                              Chrome trace_event JSON
                                              (Perfetto) on demand or on
                                              crash, and places the spans
                                              on a JAX profile's clock
  collect_status, STATUS_SCHEMA    (status)   the host-side truth served
                                              by the wire STATUS frame
                                              (EPWC op 5): occupancy,
                                              queues, credit, degrade,
                                              seq cursors, STATUS_REASONS

``python -m repro.obs.dump trace.json`` summarizes a flight dump.

Lazy exports, same pattern as :mod:`repro.serve`: ``metrics`` and
``trace`` are stdlib-only leaves; ``status`` touches the wire codec and
must not be pulled in by a bare ``import repro.obs``.
"""

from __future__ import annotations

_LAZY = {
    "Counter": "repro.obs.metrics",
    "Gauge": "repro.obs.metrics",
    "Histogram": "repro.obs.metrics",
    "MetricsRegistry": "repro.obs.metrics",
    "counter_property": "repro.obs.metrics",
    "gauge_property": "repro.obs.metrics",
    "FlightRecorder": "repro.obs.trace",
    "NULL_SPAN": "repro.obs.trace",
    "TICK_PHASES": "repro.obs.trace",
    "self_seconds": "repro.obs.trace",
    "EVENT_NAMES": "repro.obs.trace",
    "collect_status": "repro.obs.status",
    "STATUS_SCHEMA": "repro.obs.status",
}

__all__ = list(_LAZY)


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
