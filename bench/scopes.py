"""Device time under the program's named scopes.

``repro.api.stages.StageGraph`` runs each stage under
``jax.named_scope(<stage name>)`` (``bypass``, ``depth``, ``saliency``,
``tsrc``, ...).  An operation is under a scope when one ``/``-separated
component of its scope path
(``jit(masked)/vmap()/while/body/closed_call/tsrc/reshape:``) is the
scope's name; the path is looked for in the ``XLA Ops`` event's name
and its text stats.  A TPU profile keeps the path in the ``tf_op`` stat
of the event's metadata, which ``jax.profiler.ProfileData`` does not
expose, so a :class:`bench.tracing.Trace` loaded by ``tracing.load``
holds no path until those stats are merged into its events
(``scripts/trace_probe.py`` does so).  A program without the scopes
gives no time.
"""

from __future__ import annotations

from typing import Dict, Sequence

from bench import tracing
from bench.stats import interval_union


def components(e: tracing.Event) -> set:
    """The ``/``-separated components of the event's name and text
    stats."""
    out = set()
    for v in (e.name, *e.stats.values()):
        if isinstance(v, str) and "/" in v:
            out.update(v.split("/"))
    return out


def seconds_under(trace: tracing.Trace, scope: str) -> float:
    """Union of the device time of the operations under ``scope``,
    averaged over the traced chips."""
    if not trace.device:
        return 0.0
    total = 0.0
    for events in trace.device.values():
        spans = [(e.t0, e.t1) for e in events if scope in components(e)]
        total += sum(b - a for a, b in interval_union(spans, 0.0, trace.window_s))
    return total / len(trace.device)


def table(trace: tracing.Trace, scopes: Sequence[str]) -> Dict[str, float]:
    """Device seconds of each scope in ``scopes`` (a union per scope),
    and under ``"(none)"`` the union of the operations under none."""
    out = {s: seconds_under(trace, s) for s in scopes}
    none = 0.0
    for events in trace.device.values():
        spans = [(e.t0, e.t1) for e in events if not components(e) & set(scopes)]
        none += sum(b - a for a, b in interval_union(spans, 0.0, trace.window_s))
    out["(none)"] = none / max(1, len(trace.device))
    return out
