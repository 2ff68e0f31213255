"""SlottedPool — a fixed-capacity, jit-stable live pool of sessions.

:class:`repro.api.pool.StreamPool` batches a *static* population: N
streams admitted together, stepped in lock-step forever.  A live server
needs churn — streams joining and leaving at arbitrary ticks — without
ever retracing the serving program.  ``SlottedPool`` provides that as a
thin layer over the same vmapped / ``shard_map``-sharded step:

* the pool holds ``capacity`` **slots**; every pool program (step,
  admit, evict) is compiled for the full capacity, so its shapes never
  depend on how many streams are live;
* each slot carries an ``active`` flag and a **generation** counter in
  device state (one more ``(capacity,)`` leaf next to the stacked
  session states — the same leading-axis layout, so the mesh path
  shards everything with one prefix spec);
* ``step`` runs the compressor on *every* slot and keeps an inactive
  slot's previous state via a masked select — inactive slots are
  no-ops whose donated buffers are preserved in place, so admission
  and eviction are O(1) scatters that never reallocate or retrace;
* ``admit`` writes a fresh ``compressor.init()`` into a free slot
  (one traced-index scatter, compiled once for all slots) and bumps
  the slot's generation; ``evict`` clears the flag and leaves the
  state bytes behind as masked garbage.

Bitwise contract (pinned in ``tests/test_serve.py``): a slot stepped
with mask=True behaves exactly like an independent session — evicting
a slot and re-admitting into it reproduces a fresh session bit for
bit, and inactive slots never perturb active ones.

Rung-bucketed dispatch for per-stream adaptive K is built on
:meth:`step`'s ``step_fn``/``key`` hooks: the server runs one
full-capacity masked step per *rung in use* (mask = slots on that
rung), each compiled once and cached under its key — churning which
slots sit on which rung only changes mask *values*, never shapes.
:meth:`step_multi` is the coalesced variant: several rung bodies fused
into **one** dispatch (one program, one donated in/out pass), each slot
still stepped by exactly its own rung's body — bitwise identical to the
sequence of per-rung dispatches, because a vmapped step is elementwise
across slots and the rung masks are disjoint.

Speculative admission: the pool caches one **fresh-session slot image**
on device at construction (``fresh=``, shareable across the tiers of a
:class:`~repro.serve.tiers.TieredPool`), so every ``admit`` is a
device-side scatter of that cached image — ``compressor.init()`` runs
once per pool (or once per *server* when tiers share the image), never
per admission.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.api.types import SensorChunk

Array = jax.Array

# Session id used (and released) by ``SlottedPool.prewarm``.
_PREWARM_SENTINEL = "__prewarm__"


class StaleSlotError(KeyError):
    """A cached ``(slot, generation)`` handle outlived its occupant."""


class SlotStates(NamedTuple):
    """Device state of a :class:`SlottedPool`.

    Every leaf carries the leading ``(capacity, ...)`` slot axis —
    including the two bookkeeping leaves — so one prefix
    ``PartitionSpec`` shards the whole pool over a stream mesh.
    """

    sessions: Any  # stacked per-slot session states
    active: Array  # (capacity,) bool — slot holds a live stream
    generation: Array  # (capacity,) int32 — bumped on every admit


def _mask_like(mask: Array, leaf: Array) -> Array:
    """Broadcast a ``(capacity,)`` mask against a ``(capacity, ...)`` leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


class SlottedPool:
    """A live, fixed-capacity pool of compressor sessions.

    Unlike ``StreamPool`` this object is *stateful*: it owns the device
    :class:`SlotStates` (``self.states``) plus the host-side slot
    allocation table, because admission order is inherently host-driven
    state.  All device programs stay pure and jit-compiled once.

    Args:
      compressor: the session implementation filling the slots.
      capacity: number of slots (the compiled batch width).
      mesh / axis: optional stream mesh, as in ``StreamPool`` — the
        masked step is ``shard_map``-ed over the slot axis; ``capacity``
        must divide evenly over the axis size.
      donate: donate carried state to each step (default: on for
        accelerator backends).
      fresh: optional pre-built fresh-session state (the speculative
        admission image).  A :class:`~repro.serve.tiers.TieredPool`
        builds it once and shares it across all tiers; ``None`` calls
        ``compressor.init()`` once here.
    """

    def __init__(
        self,
        compressor,
        capacity: int,
        *,
        mesh: Optional[Mesh] = None,
        axis: Optional[str] = None,
        donate: Optional[bool] = None,
        fresh: Optional[Any] = None,
    ):
        if getattr(compressor, "k_ladder", None) is not None:
            raise ValueError(
                "SlottedPool slots run one lock-step program; give it a "
                "fixed-K compressor and drive per-slot rungs through "
                "repro.serve.StreamServer's bucketed dispatch"
            )
        self.compressor = compressor
        self.capacity = capacity
        self.mesh = mesh
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = donate
        if mesh is not None:
            self.axis = axis if axis is not None else mesh.axis_names[0]
            if self.axis not in mesh.axis_names:
                raise ValueError(
                    f"axis {self.axis!r} not in mesh axes {mesh.axis_names}"
                )
            n_shards = mesh.shape[self.axis]
            if capacity % n_shards != 0:
                raise ValueError(
                    f"capacity={capacity} must divide evenly over the "
                    f"{n_shards}-way {self.axis!r} mesh axis"
                )
            self._sharding = NamedSharding(mesh, PartitionSpec(self.axis))
        else:
            self.axis = None
            self._sharding = None

        # Host mirror of the allocation state (the device `active` mask
        # is authoritative for compute; this mirror avoids a host sync
        # on every admit decision).
        self.session_at: List[Optional[Hashable]] = [None] * capacity
        self._slot_of: Dict[Hashable, int] = {}
        self._host_generation: List[int] = [0] * capacity
        self._fresh = compressor.init() if fresh is None else fresh
        self._steps: Dict[Hashable, Callable] = {}
        self._admit_fn: Optional[Callable] = None
        self._evict_fn: Optional[Callable] = None
        self.states = self._init_states()

    # -- construction --------------------------------------------------------

    def _init_states(self) -> SlotStates:
        states = SlotStates(
            sessions=jax.tree.map(
                lambda x: jnp.repeat(x[None], self.capacity, axis=0),
                self._fresh,
            ),
            active=jnp.zeros((self.capacity,), bool),
            generation=jnp.zeros((self.capacity,), jnp.int32),
        )
        return self.place(states)

    def place(self, tree):
        """Put a slot-axis-leading pytree where this pool keeps its slot
        state: sharded over the mesh's slot axis, or on the default
        device when the pool has no mesh."""
        return jax.device_put(tree, self._sharding)

    # -- slot allocation (host) ----------------------------------------------

    @property
    def n_active(self) -> int:
        return len(self._slot_of)

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.session_at) if s is None]

    def slot_of(self, session_id: Hashable) -> int:
        try:
            return self._slot_of[session_id]
        except KeyError:
            raise KeyError(
                f"session {session_id!r} is not admitted; live sessions: "
                f"{sorted(map(repr, self._slot_of))}"
            ) from None

    def generation_of(self, slot: int) -> int:
        return self._host_generation[slot]

    def _host_bind(self, slot: int, session_id: Hashable) -> None:
        """Host-side slot assignment (shared by admit and the tiered
        pool's migration scatter — the device generation bump must
        always be mirrored here)."""
        self.session_at[slot] = session_id
        self._slot_of[session_id] = slot
        self._host_generation[slot] += 1

    def _host_unbind(self, slot: int) -> None:
        del self._slot_of[self.session_at[slot]]
        self.session_at[slot] = None

    # -- admission / eviction ------------------------------------------------

    def admit(self, session_id: Hashable, slot: Optional[int] = None) -> int:
        """Admit a new stream: write a fresh session into a free slot.

        Returns the slot index.  Raises ``RuntimeError`` when the pool
        is full (callers wanting LRU-style admission evict first — see
        ``StreamServer``) and ``ValueError`` on a duplicate session id.
        """
        if session_id in self._slot_of:
            raise ValueError(f"session {session_id!r} already admitted")
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError(
                    f"pool full: all {self.capacity} slots active"
                )
            slot = free[0]
        elif self.session_at[slot] is not None:
            raise ValueError(
                f"slot {slot} still holds session "
                f"{self.session_at[slot]!r}; evict it first"
            )
        self._ensure_lifecycle_fns()
        self.states = self._admit_fn(
            self.states, jnp.int32(slot), self._fresh
        )
        self._host_bind(slot, session_id)
        return slot

    def _ensure_lifecycle_fns(self) -> None:
        if self._admit_fn is None:

            def _admit(states: SlotStates, s, fresh) -> SlotStates:
                return SlotStates(
                    sessions=jax.tree.map(
                        lambda buf, one: jax.lax.dynamic_update_index_in_dim(
                            buf, one, s, 0
                        ),
                        states.sessions,
                        fresh,
                    ),
                    active=states.active.at[s].set(True),
                    generation=states.generation.at[s].add(1),
                )

            self._admit_fn = jax.jit(
                _admit, donate_argnums=(0,) if self._donate else ()
            )
        if self._evict_fn is None:

            def _evict(states: SlotStates, s) -> SlotStates:
                return states._replace(active=states.active.at[s].set(False))

            self._evict_fn = jax.jit(
                _evict, donate_argnums=(0,) if self._donate else ()
            )

    def prewarm(self) -> None:
        """Compile the admit/evict scatters ahead of the first real
        admission (speculative admission: the first user-visible admit
        pays a device-side copy, not a trace+compile).  Runs one
        admit/evict round trip on slot 0 through a sentinel binding —
        the slot ends free; only its generation counter advances."""
        if self.session_at[0] is not None:
            raise RuntimeError("prewarm() must run before any admission")
        self.admit(_PREWARM_SENTINEL, slot=0)
        self.evict(0)

    def evict(self, slot: int) -> None:
        """Deactivate a slot.  Its state bytes stay in place (masked
        no-op from now on); the next ``admit`` into it overwrites them."""
        if self.session_at[slot] is None:
            raise ValueError(f"slot {slot} is already free")
        self._ensure_lifecycle_fns()
        self.states = self._evict_fn(self.states, jnp.int32(slot))
        self._host_unbind(slot)

    def evict_session(self, session_id: Hashable) -> int:
        slot = self.slot_of(session_id)
        self.evict(slot)
        return slot

    # -- stepping ------------------------------------------------------------

    def _build_step(self, step_fn: Callable) -> Callable:
        vstep = jax.vmap(step_fn)

        def masked(states: SlotStates, chunks: SensorChunk, mask: Array):
            # The caller's mask can only narrow the live population: an
            # evicted slot stays a no-op even if a stale mask bit says
            # otherwise (and the default all-true mask means "every
            # active slot" without aliasing the donated active buffer).
            mask = mask & states.active
            new_sessions, stats = vstep(states.sessions, chunks)
            sessions = jax.tree.map(
                lambda new, old: jnp.where(_mask_like(mask, new), new, old),
                new_sessions,
                states.sessions,
            )
            stats = jax.tree.map(
                lambda s: jnp.where(
                    _mask_like(mask, s), s, jnp.zeros_like(s)
                ),
                stats,
            )
            return states._replace(sessions=sessions), stats

        if self.mesh is not None:
            spec = PartitionSpec(self.axis)
            masked = jax.shard_map(
                masked,
                mesh=self.mesh,
                in_specs=(spec, spec, spec),
                out_specs=(spec, spec),
                check_vma=False,
            )
        return jax.jit(
            masked, donate_argnums=(0,) if self._donate else ()
        )

    def _build_multi_step(self, step_fns) -> Callable:
        """One jitted program that applies ``step_fns[i]`` to the slots
        of ``masks[i]`` — the rung scheduler's coalesced dispatch.

        Each body runs over the full capacity and a per-slot masked
        select keeps exactly its own group's result, so the program is
        bitwise identical to dispatching the groups one at a time
        (vmapped bodies are elementwise across slots and the masks are
        disjoint) while paying one dispatch and one donated state pass.
        """
        vsteps = [jax.vmap(fn) for fn in step_fns]

        def masked(states: SlotStates, chunks: SensorChunk, masks: Array):
            sessions = states.sessions
            out_stats = None
            for i, vstep in enumerate(vsteps):
                mask = masks[i] & states.active
                new_sessions, stats = vstep(states.sessions, chunks)
                sessions = jax.tree.map(
                    lambda new, old, m=mask: jnp.where(
                        _mask_like(m, new), new, old
                    ),
                    new_sessions,
                    sessions,
                )
                stats = jax.tree.map(
                    lambda s, m=mask: jnp.where(
                        _mask_like(m, s), s, jnp.zeros_like(s)
                    ),
                    stats,
                )
                if out_stats is None:
                    out_stats = stats
                else:
                    out_stats = jax.tree.map(
                        lambda a, b: a | b if a.dtype == bool else a + b,
                        out_stats,
                        stats,
                    )
            return states._replace(sessions=sessions), out_stats

        if self.mesh is not None:
            spec = PartitionSpec(self.axis)
            masked = jax.shard_map(
                masked,
                mesh=self.mesh,
                in_specs=(spec, spec, PartitionSpec(None, self.axis)),
                out_specs=(spec, spec),
                check_vma=False,
            )
        return jax.jit(
            masked, donate_argnums=(0,) if self._donate else ()
        )

    def step_multi(
        self,
        chunks: SensorChunk,
        masks: Array,
        step_fns,
        key: Hashable,
    ) -> Any:
        """Coalesced step: ``len(step_fns)`` disjoint slot groups, one
        dispatch.  ``masks`` is ``(n_groups, capacity)`` bool, row ``i``
        selecting the slots stepped by ``step_fns[i]``; ``key``
        identifies the compiled combination (e.g. the tuple of rung
        K's) in the same per-variant cache :meth:`step` uses.  Returns
        the combined stats pytree, zeroed outside the mask union."""
        fn = self._steps.get(key)
        if fn is None:
            fn = self._build_multi_step(tuple(step_fns))
            self._steps[key] = fn
        self.states, stats = fn(self.states, chunks, masks)
        return stats

    def _get_step(
        self, key: Hashable, step_fn: Optional[Callable]
    ) -> Callable:
        fn = self._steps.get(key)
        if fn is None:
            fn = self._build_step(
                self.compressor.step if step_fn is None else step_fn
            )
            self._steps[key] = fn
        return fn

    def step(
        self,
        chunks: SensorChunk,
        *,
        mask: Optional[Array] = None,
        step_fn: Optional[Callable] = None,
        key: Hashable = None,
    ) -> Any:
        """Ingest one chunk per slot through a masked full-capacity step.

        ``chunks`` carries the leading ``(capacity, T, ...)`` slot axis
        (inactive / idle slots receive placeholder rows — their compute
        is discarded by the mask).  ``mask`` defaults to every active
        slot; a serving layer narrows it (e.g. to the slots on one
        adaptive-K rung, or the slots with pending data).  The device
        ``active`` flags are always intersected in-program, so a mask
        can never step an evicted slot.

        ``step_fn``/``key`` select a step *variant*: ``key`` identifies
        the compiled program in the pool's cache, ``step_fn`` supplies
        its per-session body on first use (default: the pool
        compressor's ``step``).  Each variant compiles exactly once per
        chunk shape — mask and state values never retrace.

        Returns the per-frame stats pytree, ``(capacity, T, ...)``,
        zeroed on masked-out slots.  ``self.states`` is updated in
        place.
        """
        if (
            chunks.frames.ndim != 5
            or chunks.frames.shape[0] != self.capacity
        ):
            raise ValueError(
                f"SlottedPool({self.capacity}) expects chunk arrays with "
                f"a leading slot axis, frames (capacity, T, H, W, 3); got "
                f"frames shape {tuple(chunks.frames.shape)}"
            )
        if mask is None:
            mask = self._all_slots_mask()
        self.states, stats = self._get_step(key, step_fn)(
            self.states, chunks, mask
        )
        return stats

    def _all_slots_mask(self) -> Array:
        mask = getattr(self, "_ones_mask", None)
        if mask is None:
            mask = self.place(jnp.ones((self.capacity,), bool))
            self._ones_mask = mask
        return mask

    def step_cache_sizes(self) -> Dict[Hashable, int]:
        """Compiled-trace count per step variant (jit cache stats) —
        the retrace telemetry the serve tests assert on."""
        return {
            k: int(fn._cache_size()) for k, fn in self._steps.items()
        }

    def block_until_ready(self) -> None:
        jax.block_until_ready(self.states.sessions)

    # -- per-slot access -----------------------------------------------------

    def slot_state(
        self, slot: int, *, expect_generation: Optional[int] = None
    ) -> Any:
        """The session state held by one slot (device slice).

        ``expect_generation`` is the staleness fence for callers that
        cached a ``(slot, generation)`` handle (wire reconnects, slot
        snapshots): if the slot has since been re-admitted or migrated
        into, the generations differ and the read fails instead of
        silently returning the *new occupant's* state.
        """
        if (
            expect_generation is not None
            and expect_generation != self._host_generation[slot]
        ):
            raise StaleSlotError(
                f"slot {slot} is at generation "
                f"{self._host_generation[slot]}, caller expected "
                f"{expect_generation}: the slot was re-admitted since "
                f"this handle was taken"
            )
        return jax.tree.map(lambda x: x[slot], self.states.sessions)

    def session_state(self, session_id: Hashable) -> Any:
        return self.slot_state(self.slot_of(session_id))

    def export(self, session_id: Hashable):
        return self.compressor.export(self.session_state(session_id))

    def tokens(self, session_id: Hashable, seq_len: int):
        return self.compressor.tokens(
            self.session_state(session_id), seq_len
        )
