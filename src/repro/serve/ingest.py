"""Double-buffered async chunk ingest.

Two pieces, both **bit-identical** to synchronous ingest (pinned in
``tests/test_serve.py``) — they only move *when* bytes cross the
host→device boundary, never what is computed:

* :class:`Prefetch` — a chunk-axis combinator (registered as
  ``"prefetch"`` in the combinator registry, next to the frame-axis
  ``"gated"``): wraps any iterable of :class:`~repro.api.types.
  SensorChunk` and keeps ``depth`` chunks in flight with
  ``jax.device_put`` issued *ahead* of consumption.  Because jax
  dispatch is asynchronous, the transfer of chunk ``i+1`` overlaps the
  scan of chunk ``i`` — the classic double buffer at ``depth=1``.

* :class:`ChunkQueue` — the server-side bounded per-stream queue.  A
  live stream pushes chunks as its sensors produce them; the serving
  tick pops at most one per stream.  When a producer outruns the
  server, the queue applies **backpressure**: the push is refused and
  counted (``n_overflow``) instead of growing host memory without
  bound.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Deque, Iterable, Iterator, Optional, Tuple

import jax

from repro.api.registry import register_combinator
from repro.api.types import SensorChunk


@register_combinator("prefetch")
class Prefetch:
    """Iterate chunks with host→device transfer running ahead.

    Args:
      chunks: the upstream chunk source (any iterable of pytrees; the
        canonical payload is :class:`SensorChunk`).
      depth: how many chunks to keep in flight beyond the one being
        consumed (``1`` = double buffering).
      sharding: optional target sharding/device for ``jax.device_put``
        (e.g. a pool's stream-axis ``NamedSharding``); ``None`` puts to
        the default device.

    ``device_put`` only stages a copy of the same values, so iterating
    through a ``Prefetch`` is bit-identical to iterating the source —
    the combinator is pure overlap.
    """

    name = "prefetch"

    def __init__(
        self,
        chunks: Iterable[Any],
        *,
        depth: int = 1,
        sharding: Optional[Any] = None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.chunks = chunks
        self.depth = depth
        self.sharding = sharding

    def _put(self, chunk: Any) -> Any:
        return jax.tree.map(
            lambda x: jax.device_put(x, self.sharding), chunk
        )

    def __iter__(self) -> Iterator[Any]:
        buf: Deque[Any] = deque()
        for chunk in self.chunks:
            buf.append(self._put(chunk))
            if len(buf) > self.depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()


_QUEUE_POLICIES = ("refuse", "drop_oldest")


class ChunkQueue:
    """Bounded FIFO of pending :class:`SensorChunk` for one stream.

    ``maxlen`` bounds host memory per stream.  A push onto a full queue
    follows ``policy``:

    * ``"refuse"`` (default): the *new* chunk is refused (``push``
      returns ``False``) and counted in ``n_overflow`` — the server
      surfaces the aggregate as its backpressure telemetry (a wire
      producer sees it as a NACK and retries);
    * ``"drop_oldest"``: the *oldest* queued chunk is discarded to
      admit the new one (``push`` returns ``True``; the drop is counted
      in ``n_dropped``) — freshest-data-wins for latency-sensitive
      streams that would rather skip frames than fall behind.

    Every entry records its enqueue timestamp (``clock()``, default
    ``time.monotonic``), so latency telemetry can split queueing delay
    from compute delay; ``pop_entry`` hands the timestamp back with the
    chunk while ``pop`` keeps the legacy chunk-only signature.

    Entries may additionally carry a **logical tick stamp** (``push``'s
    ``tick`` argument; the server stamps its ``n_ticks``) and the wire
    ``seq`` the chunk arrived under (``push``'s ``seq``; ``pop_record``
    hands it back, so the tick that pops the chunk can name it).
    :meth:`shed_stale` drops queued chunks whose stamp has fallen
    behind a staleness deadline — the graceful-degradation
    controller's load-shedding primitive.  Ticks, not wall seconds,
    so shed counts are deterministic for a deterministic chunk/tick
    sequence.
    """

    def __init__(
        self,
        maxlen: int = 2,
        *,
        policy: str = "refuse",
        clock: Callable[[], float] = time.monotonic,
    ):
        if maxlen < 1:
            raise ValueError(f"queue maxlen must be >= 1, got {maxlen}")
        if policy not in _QUEUE_POLICIES:
            raise ValueError(
                f"unknown queue policy {policy!r}; "
                f"available: {_QUEUE_POLICIES}"
            )
        self.maxlen = maxlen
        self.policy = policy
        self.clock = clock
        self._q: Deque[
            Tuple[SensorChunk, float, Optional[int], Optional[int]]
        ] = deque()
        self.n_pushed = 0
        self.n_overflow = 0
        self.n_dropped = 0
        self.n_shed = 0

    def __len__(self) -> int:
        return len(self._q)

    def push(
        self,
        chunk: SensorChunk,
        *,
        ts: Optional[float] = None,
        tick: Optional[int] = None,
        seq: Optional[int] = None,
    ) -> bool:
        if len(self._q) >= self.maxlen:
            if self.policy == "refuse":
                self.n_overflow += 1
                return False
            self._q.popleft()
            self.n_dropped += 1
        self._q.append((chunk, self.clock() if ts is None else ts, tick, seq))
        self.n_pushed += 1
        return True

    def pop(self) -> Optional[SensorChunk]:
        return self._q.popleft()[0] if self._q else None

    def pop_entry(self) -> Optional[Tuple[SensorChunk, float]]:
        """Pop ``(chunk, enqueue_ts)`` — ``None`` when empty."""
        entry = self._q.popleft() if self._q else None
        return None if entry is None else (entry[0], entry[1])

    def pop_full(self) -> Optional[Tuple[SensorChunk, float, Optional[int]]]:
        """Pop ``(chunk, enqueue_ts, enqueue_tick)`` — ``None`` when
        empty; the tick is ``None`` for unstamped pushes."""
        return self._q.popleft()[:3] if self._q else None

    def pop_record(
        self,
    ) -> Optional[Tuple[SensorChunk, float, Optional[int], Optional[int]]]:
        """Pop ``(chunk, enqueue_ts, enqueue_tick, seq)`` — ``None`` when
        empty; the seq is ``None`` for pushes without one."""
        return self._q.popleft() if self._q else None

    def shed_stale(self, before_tick: int) -> int:
        """Drop queued chunks stamped before ``before_tick`` (FIFO, so
        stale entries are always at the head).  Unstamped entries are
        never shed.  Returns the number dropped (also ``n_shed``)."""
        n = 0
        while (
            self._q
            and self._q[0][2] is not None
            and self._q[0][2] < before_tick
        ):
            self._q.popleft()
            self.n_shed += 1
            n += 1
        return n

    def peek(self) -> Optional[SensorChunk]:
        return self._q[0][0] if self._q else None
