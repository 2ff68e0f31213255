"""Per-tick and per-chunk span tracing into a bounded flight recorder.

The serving tick has four phases — **ingest** (degrade policy + queue
pops), **schedule** (the rung scheduler's plan), **dispatch** (the
masked pool steps) and **readback** (the tick's single batched
``device_get``) — plus two more spans: **lock_wait** (``IngestServer.
tick`` from entry to holding the stream server's pool and queue locks,
before the tick's pops, recorded into the tick that follows; the queue
lock is released again before ``schedule``, so this wait is for
control frames and submits, not for a step) and **stack** (inside
``dispatch``: the row assembly and the host-to-device stack of the
tick's batch).  Discrete events are scattered through the stack:
admit/evict, promote/demote/swap migrations, rung changes, degrade
level transitions, checkpoint/resume, and wire NACKs.

A chunk's way in is recorded apart from the ticks, keyed by its wire
``(stream, seq)``: **wire.decode** (``codec.decode_message``: the CRC
and the parse, run before any lock), **wire.lock_wait** (``IngestServer.
handle_message`` from the end of the decode to holding the stream
server's queue lock, which no tick holds across its step) and
**queue.wait** (enqueue to the pop of the tick that steps it).  Spans
from the socket threads so never fall into whichever tick happens to be
open; the popping tick lists the chunk records it popped under
``"chunks"``.

:class:`FlightRecorder` records all of it host-side into two bounded
rings — ticks, and chunk records, each ``capacity`` long (old entries
fall off; memory is O(capacity), so a recorder can stay attached for an
all-day soak) — and dumps the retained window as Chrome
``trace_event`` JSON: load the file at ``ui.perfetto.dev`` (or
``chrome://tracing``), or summarize it with
``python -m repro.obs.dump trace.json``.  :meth:`FlightRecorder.
spans_on_profile` places the retained spans on a JAX profile's clock.

Wired into :class:`repro.runtime.fault.FailureInjector`, every
fault-soak kill point dumps the last N ticks before the injected
``WorkerFailure`` propagates — a post-mortem for every crash the soak
exercises.

Recording contract: everything here is host-side Python appending to
lists — no device syncs, no jax imports — so attaching a recorder
cannot violate the one-``device_get``-per-tick or zero-retrace serving
contracts.  Thread-safety: recording appends under a lock (the wire
server's socket threads record chunk spans and NACK events while the
tick thread owns the tick spans).
"""

from __future__ import annotations

import bisect
import json
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

#: Span names of the serving tick's phases, in order.
TICK_PHASES = ("ingest", "schedule", "dispatch", "readback")

#: A span as :meth:`FlightRecorder.spans_on_profile` gives it: name,
#: start and end in seconds from the profile's start, and its ids.
ProfileSpan = Tuple[str, float, float, Dict[str, Any]]

#: Discrete event taxonomy (events outside this set are allowed — the
#: tuple documents the vocabulary the serving stack itself emits).
EVENT_NAMES = (
    "admit", "evict", "promote", "demote", "swap", "rung_change",
    "degrade_level", "checkpoint", "resume", "nack",
)


class _Span:
    """Context manager recording one closed interval into a tick."""

    __slots__ = ("_rec", "name", "t0")

    def __init__(self, rec: "FlightRecorder", name: str):
        self._rec = rec
        self.name = name
        self.t0 = 0.0

    def __enter__(self) -> "_Span":
        self.t0 = self._rec._clock()
        return self

    def __exit__(self, *exc) -> None:
        self._rec._add_span(self.name, self.t0, self._rec._clock())


class _NullSpan:
    """The recorder-detached no-op (shared instance, zero state)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


def _anchor_ns() -> int:
    """``time.time_ns() - time.monotonic_ns()``, read back to back: the
    wall-clock nanoseconds of ``time.monotonic`` zero."""
    return time.time_ns() - time.monotonic_ns()


class FlightRecorder:
    """Bounded ring buffers of traced serving ticks and chunk records.

    Args:
      capacity: ticks retained, and chunk records retained (older ones
        fall off each ring).
      clock: monotonic seconds source (injectable for deterministic
        tests).  :meth:`spans_on_profile` assumes the default,
        ``time.monotonic``, which is also the queue's enqueue clock.
    """

    def __init__(
        self,
        capacity: int = 64,
        *,
        clock: Callable[[], float] = time.monotonic,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock
        self._lock = threading.Lock()
        self._ticks: deque = deque(maxlen=capacity)
        self._cur: Optional[Dict[str, Any]] = None
        # Spans measured before the tick they belong to opens.
        self._carry: List[Tuple[str, float, float]] = []
        # Chunk records, oldest first, and the open (not yet popped)
        # record of each wire key; the key index only ever names
        # records still in the ring.
        self._chunks: deque = deque()
        self._chunk_of: Dict[Tuple[Hashable, int], Dict[str, Any]] = {}
        # Events emitted outside any open tick (checkpoint/restore on a
        # quiesced server, NACKs before the first tick): bounded too.
        self._orphans: deque = deque(maxlen=256)
        self.n_ticks_recorded = 0
        self.n_chunks_recorded = 0
        self.n_spans = 0
        self.n_events = 0

    # -- recording -----------------------------------------------------------

    def now(self) -> float:
        """The recorder's clock, for spans timed outside a ``with``
        block (a lock wait)."""
        return self._clock()

    def begin_tick(self, tick: int) -> None:
        """Open tick ``tick``; auto-closes a still-open predecessor.

        The tick stores the wall-clock anchor of the monotonic clock
        (``anchor_ns``) read as it opens, and takes the spans carried
        over by :meth:`carry_span`."""
        with self._lock:
            self._close_cur_locked()
            self._cur = {
                "tick": int(tick),
                "t0": self._clock(),
                "anchor_ns": _anchor_ns(),
                "spans": self._carry,
                "events": [],
                "chunks": [],
            }
            self._carry = []

    def end_tick(self) -> None:
        with self._lock:
            self._close_cur_locked()

    def _close_cur_locked(self) -> None:
        cur = self._cur
        if cur is None:
            return
        cur["t1"] = self._clock()
        self._ticks.append(cur)
        self.n_ticks_recorded += 1
        self._cur = None

    def span(self, name: str) -> _Span:
        """``with recorder.span("dispatch"): ...`` — one phase span."""
        return _Span(self, name)

    def _add_span(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            if self._cur is not None:
                self._cur["spans"].append((name, t0, t1))
                self.n_spans += 1

    def carry_span(self, name: str, t0: float, t1: float) -> None:
        """Record a span into the next tick that opens (the ingest
        server's lock wait, measured before the tick begins)."""
        with self._lock:
            self._carry.append((name, t0, t1))
            self.n_spans += 1

    def chunk_spans(
        self,
        stream: Hashable,
        seq: Optional[int],
        *spans: Tuple[str, float, float],
        tick: Optional[int] = None,
    ) -> None:
        """Record ``(name, t0, t1)`` spans of chunk ``(stream, seq)``.

        Spans of one key join one record until a tick pops the chunk
        (``tick`` given): that closes the record and lists it under the
        open tick's ``"chunks"``.  A chunk with no wire seq (submitted
        directly, or restored from a checkpoint) gets a record of its
        own.
        """
        with self._lock:
            key = None if seq is None else (stream, seq)
            rec = None if key is None else self._chunk_of.get(key)
            if rec is None:
                rec = {"stream": stream, "seq": seq, "tick": None, "spans": []}
                if len(self._chunks) == self.capacity:
                    old = self._chunks.popleft()
                    old_key = (old["stream"], old["seq"])
                    if self._chunk_of.get(old_key) is old:
                        del self._chunk_of[old_key]
                self._chunks.append(rec)
                self.n_chunks_recorded += 1
                if key is not None:
                    self._chunk_of[key] = rec
            rec["spans"].extend(spans)
            self.n_spans += len(spans)
            if tick is not None:
                rec["tick"] = int(tick)
                if key is not None:
                    self._chunk_of.pop(key, None)
                if self._cur is not None:
                    self._cur["chunks"].append(rec)

    def event(self, name: str, **args: Any) -> None:
        """Record one instant event (into the open tick, else the
        orphan buffer).  ``args`` values should be JSON-safe; session
        ids and labels are stringified on dump, not here."""
        t = self._clock()
        with self._lock:
            entry = (name, t, args)
            if self._cur is not None:
                self._cur["events"].append(entry)
            else:
                self._orphans.append(entry)
            self.n_events += 1

    # -- export --------------------------------------------------------------

    def ticks(self) -> List[Dict[str, Any]]:
        """The retained window, oldest first (closed ticks only)."""
        with self._lock:
            return list(self._ticks)

    def chunks(self) -> List[Dict[str, Any]]:
        """The retained chunk records, oldest first: ``stream``, ``seq``,
        the popping ``tick`` (``None`` until popped) and ``spans``."""
        with self._lock:
            return list(self._chunks)

    def spans_on_profile(
        self, start_ns: int, t0: float, t1: float
    ) -> List[ProfileSpan]:
        """Every retained tick span and chunk span that overlaps
        ``[t0, t1]``, on a profile's clock.

        ``start_ns`` is the profile's wall-clock start (``time.time_ns``
        nanoseconds); ``t0``, ``t1`` and the returned times are seconds
        from it.  Each tick's spans are mapped through the anchor the
        tick stored as it opened; a chunk's spans through the anchor of
        the retained tick that opened nearest to them.  Tick spans are
        named ``tick.<span>`` and carry ``{"tick"}``; chunk spans keep
        their names and carry ``{"stream", "seq", "tick"}``.  Sorted by
        start.
        """
        ticks, chunks = self.ticks(), self.chunks()
        starts = [tk["t0"] for tk in ticks]
        anchors = [tk["anchor_ns"] for tk in ticks]
        now_anchor = _anchor_ns()

        def anchor(t: float) -> int:
            i = bisect.bisect_left(starts, t)
            near = [j for j in (i - 1, i) if 0 <= j < len(starts)]
            if not near:
                return now_anchor
            return anchors[min(near, key=lambda j: abs(starts[j] - t))]

        out: List[ProfileSpan] = []

        def add(name, s0, s1, a, ids):
            shift = (a - start_ns) * 1e-9
            p0, p1 = s0 + shift, s1 + shift
            if p1 >= t0 and p0 <= t1:
                out.append((name, p0, p1, ids))

        for tk, a in zip(ticks, anchors):
            for name, s0, s1 in tk["spans"]:
                add("tick." + name, s0, s1, a, {"tick": tk["tick"]})
        for rec in chunks:
            if not rec["spans"]:
                continue
            a = anchor(rec["spans"][0][1])
            ids = {"stream": rec["stream"], "seq": rec["seq"], "tick": rec["tick"]}
            for name, s0, s1 in rec["spans"]:
                add(name, s0, s1, a, ids)
        out.sort(key=lambda s: s[1])
        return out

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The retained window as Chrome ``trace_event`` JSON.

        Tick and phase spans become ``ph: "X"`` complete events
        (timestamps/durations in microseconds, as the format requires);
        discrete events become ``ph: "i"`` instants.  Open the dump at
        ``ui.perfetto.dev`` or feed it to ``python -m repro.obs.dump``.
        """
        with self._lock:
            ticks = list(self._ticks)
            if self._cur is not None:
                cur = dict(self._cur)
                cur["t1"] = self._clock()
                ticks.append(cur)
            orphans = list(self._orphans)
            chunks = list(self._chunks)
        events: List[Dict[str, Any]] = [{
            "name": "process_name",
            "ph": "M",
            "pid": 0,
            "tid": 0,
            "args": {"name": "repro.serve tick loop"},
        }]
        for t in ticks:
            events.append({
                "name": f"tick {t['tick']}",
                "cat": "tick",
                "ph": "X",
                "ts": t["t0"] * 1e6,
                "dur": max(0.0, (t["t1"] - t["t0"]) * 1e6),
                "pid": 0,
                "tid": 0,
                "args": {"tick": t["tick"]},
            })
            for name, s0, s1 in t["spans"]:
                events.append({
                    "name": name,
                    "cat": "phase",
                    "ph": "X",
                    "ts": s0 * 1e6,
                    "dur": max(0.0, (s1 - s0) * 1e6),
                    "pid": 0,
                    "tid": 1,
                    "args": {"tick": t["tick"]},
                })
            for name, ts, args in t["events"]:
                events.append(_instant(name, ts, args, tick=t["tick"]))
        for rec in chunks:
            args = {
                "stream": _jsonify(rec["stream"]), "seq": rec["seq"],
                "tick": rec["tick"],
            }
            for name, s0, s1 in rec["spans"]:
                events.append({
                    "name": name,
                    "cat": "chunk",
                    "ph": "X",
                    "ts": s0 * 1e6,
                    "dur": max(0.0, (s1 - s0) * 1e6),
                    "pid": 0,
                    "tid": 3,
                    "args": args,
                })
        for name, ts, args in orphans:
            events.append(_instant(name, ts, args))
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "source": "repro.obs.trace.FlightRecorder",
                "ticks_retained": len(ticks),
                "ticks_recorded": self.n_ticks_recorded,
                "chunks_retained": len(chunks),
            },
        }

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path``; returns the path."""
        doc = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def self_seconds(
    spans: Sequence[ProfileSpan], t0: float, t1: float
) -> Dict[str, float]:
    """Seconds of ``[t0, t1]`` each span name holds as self time.

    A span's self time is the part of its interval that none of its
    child spans covers; a child is a span with the same ids that lies
    inside it (``tick.stack`` inside ``tick.dispatch``).  ``spans`` are
    as :meth:`FlightRecorder.spans_on_profile` gives them.  Spans of
    different ticks or chunks never nest, so each counts in full.
    """
    out: Dict[str, float] = {}
    for i, (name, s0, s1, ids) in enumerate(spans):
        a, b = max(s0, t0), min(s1, t1)
        if b <= a:
            continue
        inner = sorted(
            (max(c0, a), min(c1, b))
            for j, (_, c0, c1, cids) in enumerate(spans)
            if j != i and cids == ids and s0 <= c0 and c1 <= s1
            and (c0, c1) != (s0, s1) and min(c1, b) > max(c0, a)
        )
        covered, edge = 0.0, a
        for c0, c1 in inner:
            if c1 > edge:
                covered += c1 - max(c0, edge)
                edge = c1
        out[name] = out.get(name, 0.0) + (b - a) - covered
    return out


def _jsonify(v: Any) -> Any:
    return v if isinstance(v, (int, float, bool, type(None))) else str(v)


def _instant(
    name: str, ts: float, args: Dict[str, Any], *, tick: Optional[int] = None
) -> Dict[str, Any]:
    a = {k: _jsonify(v) for k, v in args.items()}
    if tick is not None:
        a["tick"] = tick
    return {
        "name": name,
        "cat": "event",
        "ph": "i",
        "s": "t",
        "ts": ts * 1e6,
        "pid": 0,
        "tid": 2,
        "args": a,
    }
