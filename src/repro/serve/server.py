"""StreamServer — the live multi-stream serving loop.

Ties the serving runtime together (paper Figure 1's deployment: one
accelerator ingesting a churning population of glasses streams):

* a :class:`~repro.serve.slots.SlottedPool` holds the device state —
  admission/eviction are O(1) masked scatters that never retrace;
* each live stream gets a bounded :class:`~repro.serve.ingest.
  ChunkQueue` (backpressure, counted) and, with a ``k_ladder``
  configured, its own :class:`~repro.serve.adaptive.KLadderController`;
* every :meth:`tick` pops at most one pending chunk per stream,
  buckets the ready slots **by rung**, and runs one cached jitted
  full-capacity masked step per rung in use — per-stream adaptive K
  over a batched pool, with each stream's ``k_trajectory`` bitwise
  equal to a solo ``EPICCompressor`` fed the same chunks (pinned in
  ``tests/test_serve.py``);
* the tick's host sync is a single batched ``device_get``
  (:func:`repro.serve.telemetry.tick_readback`) feeding the
  controllers and the per-stream :class:`~repro.serve.telemetry.
  StreamTelemetry`;
* :meth:`drain` is the double-buffered loop: the next tick's chunks
  are queued (host→device transfer via :class:`~repro.serve.ingest.
  Prefetch` semantics) *between* dispatching the current step and its
  readback, so transfer overlaps compute.

**Tiered serving** (``ServerConfig.tiers``): the device state becomes a
:class:`~repro.serve.tiers.TieredPool` — size-classed sub-pools behind
the same facade.  A tier is stepped only when it has ready chunks, so
an idle warm tier costs zero device time: tick cost tracks the *active*
population, not the capacity.  The server rebalances every tick:
streams idle ≥ ``demote_idle_frames`` frames demote toward the cold
tier; streams whose arrival-rate EMA reaches ``promote_rate`` promote
toward the hot tier (migration is a device-side gather/scatter, swap
when the hot tier is full).  Per-stream outputs and ``k_trajectory``
stay bitwise identical to the flat pool across churn *and* migration
(pinned in ``tests/test_tiered_serve.py``) — every tier runs the same
per-session step bodies and migration copies state verbatim.

Every tick's rung dispatches are ordered (and, with ``coalesce_rungs``,
pairwise merged when the backlog is low) by a measured-cost
:class:`~repro.serve.adaptive.RungScheduler`; the tick still pays one
host sync regardless of how many tiers stepped
(:func:`~repro.serve.telemetry.tick_readback` batches the per-tier
readbacks into a single ``device_get``).

Eviction policies: ``"explicit"`` (only :meth:`close`), ``"idle"``
(streams idle ≥ ``idle_frames`` frames with nothing queued are closed
at tick end), and ``"lru"`` (a full pool evicts the least-recently-
stepped stream to admit a new one).

**Locks.**  Chunks may be submitted from other threads than the one
that ticks (:class:`~repro.wire.server.IngestServer` does), so the
server owns two reentrant locks and takes them itself, always in this
order: ``pool_lock``, then ``queue_lock``.

* ``queue_lock`` guards the queue table and every ``ChunkQueue``:
  :meth:`submit`, a tick's pops and its idle evictions take it.
* ``pool_lock`` serialises the pool: :meth:`tick` holds it throughout,
  :meth:`admit`, :meth:`close` and :meth:`locked` take it, so nothing
  reads or replaces pool or slot state under a step that is about to
  donate it.

A tick has two phases, :meth:`tick_pop` (degrade policy, one pop per
stream, under both locks) and :meth:`tick_step` (schedule, stack,
device step, readback, telemetry, under the pool lock alone);
:meth:`tick` runs them back to back, so a submit never waits for a
device step.  :meth:`locked` holds both, for a caller that composes
several operations (a wire control frame, a checkpoint snapshot).
"""

from __future__ import annotations

import contextlib
import operator
import threading
import time
from functools import reduce
from typing import (
    Any,
    Dict,
    Hashable,
    Iterable,
    List,
    NamedTuple,
    Optional,
    Tuple,
)

import jax
import jax.numpy as jnp

from repro.api.types import SensorChunk
from repro.obs.metrics import MetricsRegistry, counter_property
from repro.obs.trace import NULL_SPAN
from repro.serve.adaptive import KLadderController, RungScheduler
from repro.serve.ingest import _QUEUE_POLICIES, ChunkQueue
from repro.serve.slots import SlottedPool
from repro.serve.telemetry import StreamTelemetry, tick_readback
from repro.serve.tiers import TieredPool, validate_tiers

_EVICTION_POLICIES = ("explicit", "idle", "lru")

# Promotion-by-swap hysteresis: when the hot tier is full, a warm riser
# only trades places with the coldest hot occupant if its arrival EMA
# leads by this much — keeps two streams flapping around the threshold
# from swapping every tick.
_SWAP_MARGIN = 0.25


class ServerConfig(NamedTuple):
    """Static configuration of a :class:`StreamServer`.

    ``chunk_frames`` is the serving quantum: every submitted chunk must
    carry exactly this many frames, so every pool program compiles for
    one chunk shape.  ``k_ladder=None`` serves fixed-K; a ladder turns
    on per-stream adaptive K with rung-bucketed dispatch.
    ``queue_depth`` bounds pending chunks per stream (backpressure
    beyond it); ``queue_policy`` picks what a full queue does —
    ``"refuse"`` the new chunk (default; producers see NACKs) or
    ``"drop_oldest"`` (freshest-data-wins).  ``idle_frames`` only
    applies to the ``"idle"`` eviction policy.

    Tiered serving: ``tiers`` splits ``capacity`` into size-classed
    sub-pools (hot first; must sum to ``capacity``).  Streams idle for
    ``demote_idle_frames`` frames demote toward the cold tier; streams
    whose per-tick arrival EMA (smoothing ``arrival_alpha``) reaches
    ``promote_rate`` promote toward the hot tier.  ``coalesce_rungs``
    lets the rung scheduler merge adjacent rung dispatches when at most
    ``coalesce_backlog`` chunks are queued.  ``prewarm`` pre-compiles
    the admission/eviction/migration programs at construction so the
    first churn event pays only a device copy.

    ``k_trajectory_limit`` bounds each stream's retained
    ``k_trajectory`` history to the most recent that many entries
    (``None``, the default, keeps the exact full history — what the
    bitwise-parity tests diff).  The adaptive decision rule never reads
    the history, so bounding it cannot change behaviour, only memory.
    """

    capacity: int = 8
    chunk_frames: int = 8
    k_ladder: Optional[Tuple[int, ...]] = None
    shrink_margin: int = 2
    eviction: str = "explicit"
    idle_frames: int = 64
    queue_depth: int = 2
    queue_policy: str = "refuse"
    tiers: Optional[Tuple[int, ...]] = None
    promote_rate: float = 0.5
    arrival_alpha: float = 0.5
    demote_idle_frames: int = 32
    coalesce_rungs: bool = False
    coalesce_backlog: int = 0
    prewarm: bool = False
    k_trajectory_limit: Optional[int] = None


class StreamServer:
    """A live serving runtime over a slotted compressor pool."""

    # Registry-backed counters (PR 10): `self.n_ticks += 1` and the
    # checkpoint restore `setattr` path keep working, but the integer
    # lives in a `serve_*` MetricsRegistry cell — `server_counters()`,
    # snapshots and Prometheus export all read the same cell.
    n_ticks = counter_property("serve_ticks_total")
    n_admitted = counter_property("serve_admitted_total")
    n_evicted = counter_property("serve_evicted_total")
    n_admit_rejected = counter_property("serve_admit_rejected_total")
    n_backpressure = counter_property("serve_backpressure_total")
    n_dispatches = counter_property("serve_dispatches_total")
    frames_served = counter_property("serve_frames_served_total")
    _n_dropped_closed = counter_property("serve_dropped_closed_total")

    def __init__(
        self,
        compressor,
        config: ServerConfig = ServerConfig(),
        *,
        mesh=None,
        axis: Optional[str] = None,
        donate: Optional[bool] = None,
    ):
        if config.eviction not in _EVICTION_POLICIES:
            raise ValueError(
                f"unknown eviction policy {config.eviction!r}; "
                f"available: {_EVICTION_POLICIES}"
            )
        if config.chunk_frames < 1:
            raise ValueError(
                f"chunk_frames must be >= 1, got {config.chunk_frames}"
            )
        if (
            config.k_trajectory_limit is not None
            and config.k_trajectory_limit < 1
        ):
            raise ValueError(
                f"k_trajectory_limit must be >= 1 or None, got "
                f"{config.k_trajectory_limit}"
            )
        if config.queue_policy not in _QUEUE_POLICIES:
            # Checked here, not at admit time: a per-admit failure
            # would leave a half-admitted slot behind.
            raise ValueError(
                f"unknown queue policy {config.queue_policy!r}; "
                f"available: {_QUEUE_POLICIES}"
            )
        if getattr(compressor, "k_ladder", None) is not None:
            raise ValueError(
                "pass the ladder as ServerConfig.k_ladder, not on the "
                "compressor: the server owns one rung controller per "
                "stream (a ladder-configured compressor carries a "
                "single per-instance rung)"
            )
        if not 0.0 < config.arrival_alpha <= 1.0:
            raise ValueError(
                f"arrival_alpha must be in (0, 1], got "
                f"{config.arrival_alpha}"
            )
        self.cfg = config
        self.compressor = compressor
        # The process-wide metrics registry: every serve_* counter
        # below is a property over one of its cells, and the ingest
        # frontier adopts it so wire_* lands in the same store.  Must
        # exist before the first counter attribute is touched.
        self.metrics = MetricsRegistry()
        # Optional flight recorder (repro.obs.trace.FlightRecorder):
        # when attached, every tick records its phase spans and the
        # stack's discrete events, and every popped chunk its queue
        # wait.  ``None`` keeps the hot path at two attribute reads
        # per would-be span.
        self.recorder: Optional[Any] = None
        # The two locks of the module docstring, taken in this order.
        # Reentrant: close() runs inside a tick (idle policy) and
        # admit() (lru policy), and a caller holding locked() may tick.
        self.pool_lock = threading.RLock()
        self.queue_lock = threading.RLock()
        # True while a tick's step phase runs outside the queue lock.
        self.stepping = False
        if config.k_ladder is not None:
            if not hasattr(getattr(compressor, "cfg", None), "prefilter_k"):
                raise ValueError(
                    "k_ladder needs a compressor whose cfg carries "
                    "prefilter_k (the EPIC sparse-TRD knob); "
                    f"got {type(compressor).__name__}"
                )
            # Fail fast on ladder / margin / start-rung problems here:
            # every admit() builds a controller with exactly these
            # arguments, and a per-admit failure would leave a
            # half-admitted slot behind.
            self._make_controller(compressor, config)
        self._tiered = config.tiers is not None
        if self._tiered:
            if mesh is not None:
                raise ValueError(
                    "tiers and a stream mesh are mutually exclusive: "
                    "sharding differently-sized tiers over one stream "
                    "axis would need per-tier meshes (use the flat "
                    "pool on a mesh, or tiers on one host)"
                )
            tiers = validate_tiers(config.tiers, config.capacity)
            self.pool: Any = TieredPool(compressor, tiers, donate=donate)
        else:
            self.pool = SlottedPool(
                compressor, config.capacity,
                mesh=mesh, axis=axis, donate=donate,
            )
        if config.prewarm:
            self.pool.prewarm()
        self._sched = RungScheduler(
            coalesce=config.coalesce_rungs,
            coalesce_backlog=config.coalesce_backlog,
        )
        # Per-rung fixed-K compressors (adaptive mode), built lazily:
        # one per ladder rung, shared by every stream on that rung.
        self._rung_comps: Dict[int, Any] = {}
        self._queues: Dict[Hashable, ChunkQueue] = {}
        self._controllers: Dict[Hashable, KLadderController] = {}
        self._telemetry: Dict[Hashable, StreamTelemetry] = {}
        self.evicted: List[StreamTelemetry] = []
        self._zero_chunk: Optional[SensorChunk] = None
        # Optional wire-layer telemetry: when set (e.g. a
        # ``repro.wire.latency.LatencyRecorder``), every stepped chunk
        # reports (enqueue_ts, pop_ts, readback_ts) after the tick's
        # batched readback.  ``None`` keeps the hot path free of clock
        # reads beyond the queue's own enqueue stamp.
        self.latency: Optional[Any] = None
        # Optional graceful degradation: attach a
        # ``repro.serve.degrade.DegradeController`` and every tick
        # feeds it the backlog/arrival/service pressure signals and
        # applies its level policy (rung caps, drop-oldest + staleness
        # shedding, cold-tier deferral) before popping work.  ``None``
        # serves exactly as before.
        self.degrade: Optional[Any] = None
        self._pop_ts: Dict[Hashable, Tuple[float, float]] = {}
        self._tick_t0 = 0.0
        self._last_tick_wall: Optional[float] = None
        self.max_queue_wait_ticks = 0
        self._n_dropped_closed = 0
        self.n_ticks = 0
        self.n_admitted = 0
        self.n_evicted = 0
        self.n_admit_rejected = 0
        self.n_backpressure = 0
        self.n_dispatches = 0
        self.frames_served = 0
        # Derived quantities export as *computed* gauges: reading one
        # evaluates the same expression `server_counters()` uses, so
        # the registry can never drift from host-side truth.
        m = self.metrics
        m.gauge("serve_live_streams", fn=lambda: len(self._queues))
        m.gauge(
            "serve_dropped_total",
            fn=lambda: self._n_dropped_closed
            + sum(q.n_dropped for q in self._queues.values()),
        )
        m.gauge("serve_coalesced_total", fn=lambda: self._sched.n_coalesced)
        m.gauge(
            "serve_shed_stale_total",
            fn=lambda: 0 if self.degrade is None else self.degrade.n_shed,
        )
        m.gauge(
            "serve_degrade_level",
            fn=lambda: 0 if self.degrade is None else self.degrade.level,
        )
        m.gauge(
            "serve_migrations_total",
            fn=lambda: (
                self.pool.n_migrations + self.pool.n_swaps
                if self._tiered else 0
            ),
        )

    # -- tier plumbing -------------------------------------------------------

    def _locate(self, session_id: Hashable) -> Tuple[int, int]:
        """``(tier, local_slot)``; a flat pool is tier 0."""
        if self._tiered:
            return self.pool.locate(session_id)
        return 0, self.pool.slot_of(session_id)

    def _tier_pool(self, tier: int) -> SlottedPool:
        return self.pool.tiers[tier] if self._tiered else self.pool

    def _tier_capacity(self, tier: int) -> int:
        if self._tiered:
            return self.pool.capacities[tier]
        return self.cfg.capacity

    # -- admission / eviction ------------------------------------------------

    def admit(self, session_id: Hashable) -> int:
        """Admit a stream into a free slot (fresh session state).

        Tiered pools admit into the *coldest* tier with room — new
        streams earn the hot tier through observed arrivals.  With the
        ``"lru"`` policy a full pool evicts its least-recently stepped
        stream to make room; other policies raise ``RuntimeError``
        when full.  Returns the (global) slot.
        """
        with self.locked():
            if session_id in self._queues:
                # Must precede the LRU branch: a duplicate admit on a full
                # pool must not evict an innocent stream (or silently reset
                # the duplicate itself).
                raise ValueError(f"session {session_id!r} already admitted")
            if not self.pool.free_slots():
                if self.cfg.eviction == "lru":
                    self.close(self._lru_session())
                else:
                    self.n_admit_rejected += 1
                    raise RuntimeError(
                        f"pool full ({self.cfg.capacity} slots); close a "
                        f"stream or use the 'lru' eviction policy"
                    )
            slot = self.pool.admit(session_id)
            self._queues[session_id] = ChunkQueue(
                self.cfg.queue_depth, policy=self.cfg.queue_policy
            )
            if self.cfg.k_ladder is not None:
                self._controllers[session_id] = self._make_controller(
                    self.compressor, self.cfg
                )
            tier = self.pool.unpack_slot(slot)[0] if self._tiered else 0
            self._telemetry[session_id] = StreamTelemetry(
                session_id=session_id,
                slot=slot,
                generation=self.pool.generation_of(slot),
                admitted_tick=self.n_ticks,
                tier=tier,
            )
            self.n_admitted += 1
            self._event("admit", stream=session_id, slot=slot, tier=tier)
            return slot

    @staticmethod
    def _make_controller(compressor, config: ServerConfig):
        return KLadderController(
            config.k_ladder,
            start_k=compressor.cfg.prefilter_k,
            shrink_margin=config.shrink_margin,
            what="cfg.prefilter_k",
            history_limit=config.k_trajectory_limit,
        )

    def try_admit(self, session_id: Hashable) -> Optional[int]:
        """``admit`` that reports a full pool as ``None`` (counted)."""
        try:
            return self.admit(session_id)
        except RuntimeError:
            return None

    def close(self, session_id: Hashable) -> StreamTelemetry:
        """Explicitly evict a stream; returns its final telemetry."""
        with self.locked():
            self.pool.evict_session(session_id)
            self._n_dropped_closed += self._queues[session_id].n_dropped
            self._queues.pop(session_id)
            self._controllers.pop(session_id, None)
            tele = self._telemetry.pop(session_id)
            self.evicted.append(tele)
            self.n_evicted += 1
            self._event("evict", stream=session_id, tier=tele.tier)
            return tele

    @contextlib.contextmanager
    def locked(self):
        """Hold the pool lock, then the queue lock: no tick's step is in
        flight and no submit interleaves until the block ends."""
        with self.pool_lock, self.queue_lock:
            yield

    def _lru_session(self) -> Hashable:
        return min(
            self._telemetry.values(),
            key=lambda t: (t.last_step_tick, t.slot),
        ).session_id

    # -- ingest --------------------------------------------------------------

    def submit(
        self,
        session_id: Hashable,
        chunk: SensorChunk,
        *,
        seq: Optional[int] = None,
    ) -> bool:
        """Queue one chunk for a live stream.

        Returns ``False`` (and counts backpressure) when the stream's
        bounded queue is full — the producer should retry after a tick.
        ``seq`` is the chunk's wire seq, kept beside its enqueue stamp
        so a recorder can key the chunk's ``queue.wait`` span.
        """
        if chunk.n_frames != self.cfg.chunk_frames:
            raise ValueError(
                f"serving quantum is {self.cfg.chunk_frames} frames per "
                f"chunk, got {chunk.n_frames} (pad or re-chunk upstream)"
            )
        with self.queue_lock:
            q = self._queues.get(session_id)
            if q is None:
                raise KeyError(f"session {session_id!r} is not admitted")
            if self._zero_chunk is None:
                self._zero_chunk = jax.tree.map(jnp.zeros_like, chunk)
            ok = q.push(chunk, tick=self.n_ticks, seq=seq)
            if not ok:
                self._telemetry[session_id].n_queue_overflow += 1
                self.n_backpressure += 1
            return ok

    # -- tracing hooks -------------------------------------------------------

    def _span(self, name: str):
        """A phase span on the attached recorder, or the shared no-op
        (no allocation, no clock read) when tracing is off."""
        rec = self.recorder
        return NULL_SPAN if rec is None else rec.span(name)

    def _event(self, name: str, **args: Any) -> None:
        rec = self.recorder
        if rec is not None:
            rec.event(name, **args)

    def _tick_begin(self) -> None:
        rec = self.recorder
        if rec is not None:
            rec.begin_tick(self.n_ticks)

    # -- the serving tick ----------------------------------------------------

    def _rung_comp(self, k: int):
        comp = self._rung_comps.get(k)
        if comp is None:
            comp = type(self.compressor)(
                self.compressor.cfg._replace(prefilter_k=k),
                self.compressor.models,
            )
            self._rung_comps[k] = comp
        return comp

    def _rung_step_fn(self, k: Optional[int]):
        return self.compressor.step if k is None else self._rung_comp(k).step

    def _pop_ready(
        self, deferred: Tuple[int, ...] = ()
    ) -> Dict[Hashable, SensorChunk]:
        ready = {}
        self._pop_ts = {}
        now = time.monotonic()
        rec = self.recorder
        for sid in list(self._queues):
            if deferred and self._locate(sid)[0] in deferred:
                continue
            entry = self._queues[sid].pop_record()
            if entry is not None:
                ready[sid] = entry[0]
                self._pop_ts[sid] = (entry[1], now)
                if rec is not None:
                    rec.chunk_spans(
                        sid, entry[3], ("queue.wait", entry[1], now),
                        tick=self.n_ticks,
                    )
                if entry[2] is not None:
                    self.max_queue_wait_ticks = max(
                        self.max_queue_wait_ticks, self.n_ticks - entry[2]
                    )
        return ready

    def _degrade_step(self) -> Tuple[int, ...]:
        """Feed the attached degradation controller one tick's pressure
        signals and apply its level policy; returns the tier indices
        whose dispatch the current level defers (empty when level 0 or
        no controller).  Every action only reduces or masks work —
        capped rungs are existing ladder rungs, shedding removes queued
        chunks, deferral skips pops — so no new program shapes appear
        across level transitions.
        """
        dg = self.degrade
        if dg is None:
            return ()
        backlog = sum(len(q) for q in self._queues.values())
        capacity = max(1, len(self._queues) * self.cfg.queue_depth)
        emas = [t.arrival_ema for t in self._telemetry.values()]
        level_before = dg.level
        dg.observe(
            backlog / capacity,
            arrival_ema=sum(emas) / len(emas) if emas else 0.0,
            service_s=self._last_tick_wall,
        )
        if dg.level != level_before:
            self._event(
                "degrade_level",
                level_from=level_before, level_to=dg.level,
                pressure=round(dg.pressure, 4),
            )
        pol = dg.policy
        qpol = pol.queue_policy or self.cfg.queue_policy
        for q in self._queues.values():
            q.policy = qpol
            if pol.stale_after_ticks is not None:
                dg.n_shed += q.shed_stale(
                    self.n_ticks - pol.stale_after_ticks
                )
        if self.cfg.k_ladder is not None and self._controllers:
            cap = max(0, len(self.cfg.k_ladder) - 1 - pol.rung_cap_down)
            for ctl in self._controllers.values():
                ctl.set_rung_cap(cap)
        if self._tiered and pol.defer_tiers > 0:
            ntiers = len(self.pool.tiers)
            # Never defer the hot tier: someone must keep serving.
            return tuple(range(max(1, ntiers - pol.defer_tiers), ntiers))
        return ()

    def _slot_mask(self, tier: int, sids) -> jax.Array:
        tp = self._tier_pool(tier)
        return jnp.zeros((tp.capacity,), bool).at[
            jnp.array([tp.slot_of(s) for s in sids], jnp.int32)
        ].set(True)

    def _dispatch(self, ready: Dict[Hashable, SensorChunk]):
        """Assemble per-tier tick batches and dispatch the scheduler's
        plans — only tiers with ready chunks are stepped.  Returns the
        (still in-flight) per-tier combined stats, the ``(tier, rung)``
        session groups, and the dispatched variant keys."""
        self._tick_t0 = time.monotonic()
        with self._span("schedule"):
            groups: Dict[Tuple[int, Optional[int]], List[Hashable]] = {}
            for sid in ready:
                tier = self._locate(sid)[0]
                k = (
                    None if self.cfg.k_ladder is None
                    else self._controllers[sid].begin_chunk()
                )
                groups.setdefault((tier, k), []).append(sid)
            plans = self._sched.plan(
                groups,
                backlog=sum(len(q) for q in self._queues.values()),
            )

        with self._span("dispatch"):
            batches: Dict[int, SensorChunk] = {}
            with self._span("stack"):
                for tier in {t for t, _ in groups}:
                    rows = [self._zero_chunk] * self._tier_capacity(tier)
                    tp = self._tier_pool(tier)
                    for sid, chunk in ready.items():
                        if self._locate(sid)[0] == tier:
                            rows[tp.slot_of(sid)] = chunk
                    batches[tier] = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *rows
                    )

            stats_parts: Dict[int, List[Any]] = {}
            keys: List[Hashable] = []
            for plan in plans:
                tp = self._tier_pool(plan.tier)
                batch = batches[plan.tier]
                if len(plan.rungs) == 1:
                    k = plan.rungs[0]
                    stats = tp.step(
                        batch,
                        mask=self._slot_mask(plan.tier, plan.sids[0]),
                        step_fn=(
                            None if k is None else self._rung_comp(k).step
                        ),
                        key=k,
                    )
                else:
                    stats = tp.step_multi(
                        batch,
                        jnp.stack([
                            self._slot_mask(plan.tier, sids)
                            for sids in plan.sids
                        ]),
                        [self._rung_step_fn(k) for k in plan.rungs],
                        key=plan.key,
                    )
                keys.append(plan.key)
                self.n_dispatches += 1
                stats_parts.setdefault(plan.tier, []).append(stats)
        # Rung masks are disjoint and masked-out slots are zeroed, so
        # the union of a tier's per-rung stats is an elementwise
        # combine.
        stats_by_tier = {
            tier: jax.tree.map(
                lambda *xs: reduce(
                    jnp.logical_or if xs[0].dtype == bool else operator.add,
                    xs,
                ),
                *parts,
            )
            for tier, parts in stats_parts.items()
        }
        return stats_by_tier, groups, keys

    def _finish(self, stats_by_tier, groups, keys=()) -> None:
        """One batched readback across every stepped tier; feed
        controllers + telemetry + the scheduler's cost model; apply the
        idle eviction policy and (tiered) rebalance."""
        stepped = [sid for sids in groups.values() for sid in sids]
        if stepped:
            tiers_stepped = sorted(stats_by_tier)
            with self._span("readback"):
                rb = tick_readback(
                    [stats_by_tier[t] for t in tiers_stepped]
                )
            self._last_tick_wall = time.monotonic() - self._tick_t0
            self._sched.observe_tick(keys, self._last_tick_wall)
            base, off = {}, 0
            for t in tiers_stepped:
                base[t] = off
                off += self._tier_capacity(t)
            if self.latency is not None:
                done = time.monotonic()
                for sid in stepped:
                    ts = self._pop_ts.get(sid)
                    if ts is not None:
                        self.latency.observe(ts[0], ts[1], done)
            for sid in stepped:
                tele = self._telemetry[sid]
                tier, local = self._locate(sid)
                row = base[tier] + local
                tele.n_chunks += 1
                tele.n_frames += self.cfg.chunk_frames
                tele.n_processed += int(rb.processed[row])
                tele.n_inserted += int(rb.inserted[row])
                tele.buffer_valid = int(rb.buffer_valid[row])
                tele.idle_frames = 0
                tele.last_step_tick = self.n_ticks
                ctl = self._controllers.get(sid)
                if ctl is not None:
                    k_before = ctl.k
                    ctl.update(
                        int(rb.overflow[row]), int(rb.peak_full[row])
                    )
                    if ctl.k != k_before:
                        self._event(
                            "rung_change",
                            stream=sid, k_from=k_before, k_to=ctl.k,
                        )
                    tele.k_trajectory = ctl.k_trajectory
            self.frames_served += len(stepped) * self.cfg.chunk_frames
        stepped_set = set(stepped)
        a = self.cfg.arrival_alpha
        for sid in list(self._telemetry):
            tele = self._telemetry[sid]
            if sid not in stepped_set:
                tele.idle_frames += self.cfg.chunk_frames
            tele.arrival_ema = (1.0 - a) * tele.arrival_ema + a * float(
                sid in stepped_set
            )
        self.n_ticks += 1
        if self.cfg.eviction == "idle":
            # Under the queue lock, and only streams with nothing
            # queued: a chunk accepted while this tick stepped is
            # served by the next one, never dropped with its stream.
            with self.queue_lock:
                for sid in [
                    sid for sid, tele in self._telemetry.items()
                    if tele.idle_frames >= self.cfg.idle_frames
                    and not len(self._queues[sid])
                ]:
                    self.close(sid)
        if self._tiered:
            self._rebalance()
        if self.recorder is not None:
            self.recorder.end_tick()

    # -- tier rebalancing ----------------------------------------------------

    def _migrate(self, session_id: Hashable, to_tier: int) -> None:
        tele = self._telemetry[session_id]
        from_tier = tele.tier
        slot = self.pool.migrate(session_id, to_tier)
        tele.slot = slot
        tele.tier = to_tier
        tele.generation = self.pool.generation_of(slot)
        tele.n_migrations += 1
        self._event(
            "demote" if to_tier > from_tier else "promote",
            stream=session_id, from_tier=from_tier, to_tier=to_tier,
        )

    def _swap(self, session_a: Hashable, session_b: Hashable) -> None:
        self.pool.swap(session_a, session_b)
        self._event("swap", stream=session_a, with_stream=session_b)
        for sid in (session_a, session_b):
            slot = self.pool.slot_of(sid)
            tele = self._telemetry[sid]
            tele.slot = slot
            tele.tier = self.pool.unpack_slot(slot)[0]
            tele.generation = self.pool.generation_of(slot)
            tele.n_migrations += 1

    def _rebalance(self) -> None:
        """Concentrate active streams into the hot tier.

        Demote: a non-cold stream idle ≥ ``demote_idle_frames`` frames
        moves to the coldest tier with a free slot.  Promote: non-hot
        streams with arrival EMA ≥ ``promote_rate`` (hottest first,
        slot-order tie-break) move into the hottest tier with room, or
        swap with the coldest hot occupant when its EMA trails by
        ≥ ``_SWAP_MARGIN``.  All moves are device-side gather/scatters;
        the compiled-program set is fixed after :meth:`~repro.serve.
        tiers.TieredPool.prewarm`, so rebalancing never retraces.
        """
        pool = self.pool
        coldest = len(pool.tiers) - 1
        for tele in list(self._telemetry.values()):
            if (
                tele.tier < coldest
                and tele.idle_frames >= self.cfg.demote_idle_frames
            ):
                for tj in range(coldest, tele.tier, -1):
                    if pool.tiers[tj].free_slots():
                        self._migrate(tele.session_id, tj)
                        break
        risers = sorted(
            (
                t for t in self._telemetry.values()
                if t.tier > 0 and t.arrival_ema >= self.cfg.promote_rate
            ),
            key=lambda t: (-t.arrival_ema, t.slot),
        )
        for tele in risers:
            target = next(
                (
                    tj for tj in range(tele.tier)
                    if pool.tiers[tj].free_slots()
                ),
                None,
            )
            if target is not None:
                self._migrate(tele.session_id, target)
                continue
            victims = [
                self._telemetry[s] for s in pool.tiers[0]._slot_of
            ]
            victim = min(victims, key=lambda v: (v.arrival_ema, v.slot))
            if victim.arrival_ema + _SWAP_MARGIN <= tele.arrival_ema:
                self._swap(tele.session_id, victim.session_id)

    # -- tick / drain --------------------------------------------------------

    def tick(self, *, wait_since: Optional[float] = None) -> List[Hashable]:
        """Serve one tick: step every stream with a pending chunk.

        Holds the pool lock throughout and the queue lock for the pops
        only, so chunks are submitted while the step runs.  Returns the
        session ids stepped this tick.  A tick with no pending work
        still advances the clock and the idle accounting.

        ``wait_since`` is a reading of the attached recorder's clock
        (``recorder.now()``) taken by a caller that ticks alongside
        submitting threads: the wait from then to holding both locks
        is recorded as the tick's ``lock_wait`` span.
        """
        with self.pool_lock:
            with self.queue_lock:
                rec = self.recorder
                if rec is not None and wait_since is not None:
                    rec.carry_span("lock_wait", wait_since, rec.now())
                ready = self.tick_pop()
                self.stepping = True
            try:
                return self.tick_step(ready)
            finally:
                self.stepping = False

    def tick_pop(self) -> Dict[Hashable, SensorChunk]:
        """A tick's first phase: open its record, apply the degrade
        policy and pop at most one pending chunk per stream, under the
        queue lock.  The caller holds the pool lock across this phase
        and :meth:`tick_step`."""
        with self.queue_lock:
            self._tick_begin()
            with self._span("ingest"):
                return self._pop_ready(self._degrade_step())

    def tick_step(
        self, ready: Dict[Hashable, SensorChunk]
    ) -> List[Hashable]:
        """A tick's second phase, on what :meth:`tick_pop` returned:
        schedule, stack, device step and readback, then telemetry,
        controllers, idle eviction and rebalancing.  Needs the pool
        lock only.  Returns the session ids stepped."""
        if not ready:
            self._finish({}, {})
            return []
        stats, groups, keys = self._dispatch(ready)
        self._finish(stats, groups, keys)
        return [sid for sids in groups.values() for sid in sids]

    def drain(
        self,
        feeds: Dict[Hashable, Iterable[SensorChunk]],
        *,
        max_ticks: Optional[int] = None,
    ) -> int:
        """Double-buffered serving loop over per-stream chunk sources.

        Every iteration dispatches the current tick's pool steps, then
        — while that compute is in flight — pulls and submits the next
        chunk of every feed (the host→device transfer of tick ``i+1``
        overlaps the scan of tick ``i``; jax dispatch is async), and
        only then performs the tick's single readback.  Bit-identical
        to submit-then-tick in a strict sequence.  Returns the number
        of ticks run.
        """
        iters = {sid: iter(src) for sid, src in feeds.items()}
        with self.pool_lock:
            for sid in iters:
                if sid not in self._queues:
                    self.admit(sid)
            ticks = 0
            self._refill(iters)
            while iters or any(len(q) for q in self._queues.values()):
                ready = self.tick_pop()
                inflight = self._dispatch(ready) if ready else None
                self._refill(iters)  # overlaps the dispatched compute
                if inflight is not None:
                    self._finish(*inflight)
                else:
                    self._finish({}, {})
                ticks += 1
                if max_ticks is not None and ticks >= max_ticks:
                    break
            return ticks

    def _refill(self, iters: Dict[Hashable, Any]) -> None:
        for sid in list(iters):
            if sid not in self._queues:  # evicted mid-run: drop its feed
                del iters[sid]
                continue
            if len(self._queues[sid]) >= self.cfg.queue_depth:
                continue
            try:
                chunk = next(iters[sid])
            except StopIteration:
                del iters[sid]
                continue
            self.submit(sid, chunk)

    # -- introspection -------------------------------------------------------

    @property
    def live_sessions(self) -> List[Hashable]:
        return list(self._queues)

    def telemetry(self, session_id: Hashable) -> StreamTelemetry:
        return self._telemetry[session_id]

    def server_counters(self) -> Dict[str, int]:
        return {
            "n_ticks": self.n_ticks,
            "n_live": len(self._queues),
            "n_admitted": self.n_admitted,
            "n_evicted": self.n_evicted,
            "n_admit_rejected": self.n_admit_rejected,
            "n_backpressure": self.n_backpressure,
            "n_dropped": self._n_dropped_closed
            + sum(q.n_dropped for q in self._queues.values()),
            "n_dispatches": self.n_dispatches,
            "n_coalesced": self._sched.n_coalesced,
            "n_shed_stale": (
                0 if self.degrade is None else self.degrade.n_shed
            ),
            "degrade_level": (
                0 if self.degrade is None else self.degrade.level
            ),
            "n_migrations": (
                self.pool.n_migrations + self.pool.n_swaps
                if self._tiered else 0
            ),
            "frames_served": self.frames_served,
        }

    def step_cache_sizes(self) -> Dict[Hashable, int]:
        """Compiled-trace counts across every pool step variant — the
        zero-post-warmup-retrace telemetry (tiered pools key by
        ``(tier, variant)``)."""
        return self.pool.step_cache_sizes()

    def block_until_ready(self) -> None:
        self.pool.block_until_ready()

    def state(self, session_id: Hashable):
        return self.pool.session_state(session_id)

    def export(self, session_id: Hashable):
        return self.pool.export(session_id)

    def tokens(self, session_id: Hashable, seq_len: int):
        return self.pool.tokens(session_id, seq_len)
