"""Reduction of a JAX profiler trace to device busy time and kernel time.

The profiler writes one ``.xplane.pb`` per traced window.  It is read
with ``jax.profiler.ProfileData`` into a :class:`Trace`: the traced
window's length, the device operations of each chip (from each
``/device:TPU:<n>`` plane's ``XLA Ops`` line), and the host events
(none where the host tracer is off).  Event times are seconds from the
start of the profile, whose wall-clock time the trace records.

Everything below :func:`from_profile` works on plain tuples, so the
reduction is checked on hand-made traces.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from bench.stats import interval_union

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    name: str
    t0: float  # seconds from the profile's start
    t1: float
    stats: Dict[str, object]


class Trace(NamedTuple):
    window_s: float  # profile start to profile stop
    start_ns: int  # wall-clock (``time.time_ns``) start of the profile
    device: Dict[str, List[Event]]  # device plane name -> its operations
    host: List[Event]  # every host event (annotations, runtime calls)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"want one .xplane.pb under {logdir}, found {paths}")
    return paths[0]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        t0 = e.start_ns * 1e-9
        out.append(Event(e.name, t0, t0 + e.duration_ns * 1e-9, dict(e.stats)))
    return out


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    window_s = start_ns = None
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in profile.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                start_ns = int(st["profile_start_time"])
                window_s = (int(st["profile_stop_time"]) - start_ns) * 1e-9
        elif plane.name.startswith(DEVICE_PREFIX) and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    if window_s is None:
        raise RuntimeError("the trace names no profile start and stop time")
    return Trace(window_s, start_ns, device, host)


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(logdir)))


def busy_intervals(trace: Trace, plane: str) -> List[Tuple[float, float]]:
    """Union of the intervals in which an operation ran on one chip."""
    return interval_union(
        [(e.t0, e.t1) for e in trace.device[plane]], 0.0, trace.window_s
    )


def busy_seconds(trace: Trace) -> Optional[float]:
    """Busy seconds averaged over the traced chips; ``None`` without any."""
    if not trace.device:
        return None
    total = sum(
        b - a for p in trace.device for a, b in busy_intervals(trace, p)
    )
    return total / len(trace.device)


def _names(e: Event) -> Iterable[str]:
    yield e.name
    for v in e.stats.values():
        if isinstance(v, str):
            yield v


def kernel_seconds(trace: Trace, kernels: Sequence[str]) -> float:
    """Summed device time of the events that name one of ``kernels``,
    averaged over the traced chips."""
    if not trace.device:
        return 0.0
    total = 0.0
    for events in trace.device.values():
        for e in events:
            if any(k in n for n in _names(e) for k in kernels):
                total += e.t1 - e.t0
    return total / len(trace.device)


def op_name(e: Event) -> str:
    """An operation's HLO name without its signature: ``%while.4`` for
    ``%while.4 = (s32[], ...) while(...)``."""
    return e.name.split(" = ", 1)[0]


def top_ops(trace: Trace, n: int = 10) -> List[List[object]]:
    """The ``n`` device operations (by HLO name) that took most time,
    with their seconds averaged over the traced chips."""
    by_name: Dict[str, float] = {}
    for events in trace.device.values():
        for e in events:
            k = op_name(e)
            by_name[k] = by_name.get(k, 0.0) + (e.t1 - e.t0)
    chips = max(1, len(trace.device))
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s / chips] for name, s in ranked]


def idle_gaps(
    trace: Trace,
    host_spans: Sequence[Tuple[str, float, float]],
    n: int = 10,
    fallback: Sequence[str] = ("wire.send",),
) -> List[List[object]]:
    """The ``n`` longest idle gaps of the first chip, each named by what
    the host was doing in it: the kind of span (by name) that covers most
    of the gap, kinds in ``fallback`` only where no other kind covers any
    of it (a send that waits for the ingest lock is not the host's work),
    and ``"host_other"`` where nothing does.

    ``host_spans`` are ``(name, t0, t1)`` on the trace's clock.
    """
    if not trace.device:
        return []
    plane = sorted(trace.device)[0]
    busy = busy_intervals(trace, plane)
    gaps, edge = [], 0.0
    for a, b in busy + [(trace.window_s, trace.window_s)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:n]:
        cover: Dict[str, float] = {}
        for name, s0, s1 in host_spans:
            s = min(g1, s1) - max(g0, s0)
            if s > 0:
                cover[name] = cover.get(name, 0.0) + s
        primary = {k: v for k, v in cover.items() if k not in fallback}
        pick = primary or cover
        out.append([max(pick, key=pick.get) if pick else "host_other", g1 - g0])
    return out


def host_offset(trace: Trace, wall_minus_mono_ns: int) -> float:
    """Seconds to add to a ``time.monotonic`` reading to place it on the
    trace's clock, given ``time.time_ns() - time.monotonic_ns()`` read on
    the host while the profile ran."""
    return (wall_minus_mono_ns - trace.start_ns) * 1e-9
