"""Typed stage-graph pipeline: pluggable per-frame stages.

The per-frame work of every compression method — EPIC's bypass → depth →
HIR saliency → TSRC chain (paper Figure 3c) and the four baselines'
select → retain bodies — is expressed as an ordered composition of
:class:`FrameStage` objects threaded over a shared :class:`FrameCtx`.
The former monolithic scan bodies (``core/pipeline.process_frame``, the
baseline loop in ``api/compressor``) are now thin *graph builders*; new
stages (ablation scenarios, alternative depth/saliency modules, fused
accelerator steps) plug in by name through the stage registry
(:func:`repro.api.registry.register_stage`) without editing any scan
body.

Design constraints, in order:

1. **Bit-identical** to the monolithic pipeline: stages run exactly the
   ops the scan body ran, in the same order, and the gated region
   (depth/saliency/TSRC under the bypass ``lax.cond``) conds over
   exactly the operands the old code did.  ``tests/test_stages.py``
   pins this against pre-refactor goldens.
2. **State-layout compatible**: a graph's carried state flattens to the
   same leaves, in the same order, as the public state NamedTuples
   (``EPICState``, ``BaselineState``), so sessions, pools, checkpoints
   and tests are unaffected by the refactor.
3. jit/vmap/scan-friendly: the graph is plain Python composition at
   trace time; nothing here allocates or branches at runtime.

Each stage runs under ``jax.named_scope`` of its name, so the device
operations of a profile say which stage they belong to (``tsrc``,
``depth``, ...); the scope changes no operation.
"""

from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    NamedTuple,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    runtime_checkable,
)

import jax
import jax.numpy as jnp

from repro.api.registry import register_combinator

Array = jax.Array


class FrameCtx(NamedTuple):
    """Shared per-frame carry threaded through the stages of one frame.

    Sensor inputs (``frame``/``pose``/``gaze``/``depth``) and the frame
    clock ``t`` are set by the graph runner; stages communicate through
    the derived fields (each ``None`` until its producing stage runs)
    and accumulate per-frame counters into ``stats`` (a dict keyed by
    stage name, consumed by the graph's ``finalize``).
    """

    # -- sensor inputs for the current frame --------------------------------
    frame: Array  # (H, W, 3)
    pose: Array  # (4, 4)
    gaze: Array  # (2,)
    depth: Optional[Array]  # (H, W) oracle depth, or None
    t: Array  # scalar frame clock (graph-owned)
    # -- control ------------------------------------------------------------
    process: Array  # scalar bool — downstream gate (bypass writes this)
    # -- derived products (producer stage -> consumer stage) ----------------
    dmap: Optional[Array] = None  # (H, W) predicted/oracle depth
    sal_mask: Optional[Array] = None  # (G*G,) bool SRD saliency
    sal_score: Optional[Array] = None  # (G*G,) float saliency strength
    patches: Optional[Array] = None  # (K, P, P, 3) candidate patches
    origins: Optional[Array] = None  # (K, 2) candidate origins
    keep: Optional[Array] = None  # scalar bool — retain this frame
    # -- per-stage counters --------------------------------------------------
    stats: Dict[str, Any] = {}

    def with_stat(self, name: str, value: Any) -> "FrameCtx":
        return self._replace(stats={**self.stats, name: value})


@runtime_checkable
class FrameStage(Protocol):
    """One pluggable step of a per-frame pipeline.

    ``init`` returns the stage's slice of the carried session state
    (``None`` for stateless stages); ``apply`` consumes one frame's
    :class:`FrameCtx` and returns the updated (state, ctx) pair.
    Implementations must be pure functions of their inputs so the graph
    stays jit/vmap/scan/differentiation-friendly.
    """

    name: str

    def init(self) -> Any:
        ...

    def apply(self, state: Any, ctx: FrameCtx) -> Tuple[Any, FrameCtx]:
        ...


@register_combinator("gated")
class Gated:
    """Combinator: run ``stages`` under ``lax.cond(ctx.process, ...)``.

    This is the stage-graph form of EPIC's frame-bypass gate: when the
    gate is closed, none of the inner stages' compute is executed (the
    cond skips it wholesale, exactly like the monolithic pipeline), the
    inner states pass through unchanged, and ``skip_stats(states, ctx)``
    supplies the stats the skipped stages would have emitted (same
    keys/shapes/dtypes, so both cond branches agree structurally).

    Only the inner states and the inner stats delta cross the cond —
    derived ``FrameCtx`` fields produced inside the gate do not escape
    it, mirroring the old code where depth/saliency existed only inside
    ``do_process``.
    """

    def __init__(
        self,
        stages: Sequence[FrameStage],
        skip_stats: Callable[[Tuple[Any, ...], FrameCtx], Dict[str, Any]],
    ):
        self.stages = tuple(stages)
        self.skip_stats = skip_stats
        self.name = "gated[" + ",".join(s.name for s in self.stages) + "]"

    def init(self) -> Tuple[Any, ...]:
        return tuple(s.init() for s in self.stages)

    def apply(
        self, states: Tuple[Any, ...], ctx: FrameCtx
    ) -> Tuple[Tuple[Any, ...], FrameCtx]:
        def run(states):
            c = ctx._replace(stats={})
            out = []
            for stage, st in zip(self.stages, states):
                st, c = _apply_scoped(stage, st, c)
                out.append(st)
            return tuple(out), c.stats

        def skip(states):
            return states, self.skip_stats(states, ctx)

        states, delta = jax.lax.cond(ctx.process, run, skip, states)
        return states, ctx._replace(stats={**ctx.stats, **delta})


def _apply_scoped(stage: FrameStage, state: Any, ctx: FrameCtx):
    """``stage.apply`` under ``jax.named_scope(stage.name)``, so the
    stage's device operations carry its name in a profile.  A
    :class:`Gated` combinator names each of its inner stages instead."""
    if isinstance(stage, Gated):
        return stage.apply(state, ctx)
    with jax.named_scope(stage.name):
        return stage.apply(state, ctx)


class StageGraph:
    """An ordered FrameStage composition + frame clock + stats finalizer.

    The carried *graph state* is ``(per_stage_states, clock)`` — a tuple
    in stage order, so its pytree leaves coincide with the public state
    NamedTuples the builders adapt to (see module docstring).

    ``finalize(ctx) -> stats`` shapes the accumulated per-stage counters
    into the method's public per-frame stats pytree.
    """

    def __init__(
        self,
        stages: Sequence[FrameStage],
        *,
        finalize: Optional[Callable[[FrameCtx], Any]] = None,
        clock_init: Callable[[], Array] = (
            lambda: jnp.zeros((), jnp.float32)
        ),
        clock_next: Callable[[Array], Array] = lambda t: t + 1.0,
    ):
        self.stages = tuple(stages)
        self.finalize = finalize
        self.clock_init = clock_init
        self.clock_next = clock_next

    # -- state management ----------------------------------------------------

    def init_state(self) -> Tuple[Tuple[Any, ...], Array]:
        return tuple(s.init() for s in self.stages), self.clock_init()

    def pack_state(
        self, values: Dict[str, Any], clock: Array
    ) -> Tuple[Tuple[Any, ...], Array]:
        """Assemble a graph state from named per-stage states.

        Every *stateful* stage (``init() is not None``) must appear in
        ``values``; stateless stages contribute ``None``.  The inverse
        of :meth:`unpack_state` — used by the thin public entry points
        to adapt their state NamedTuples onto the graph.
        """
        remaining = dict(values)

        def pack(stage) -> Any:
            if isinstance(stage, Gated):
                return tuple(pack(s) for s in stage.stages)
            if stage.name in remaining:
                return remaining.pop(stage.name)
            template = stage.init()
            if template is not None:
                raise KeyError(
                    f"stateful stage {stage.name!r} missing from pack_state "
                    f"values {sorted(values)}"
                )
            return None

        packed = tuple(pack(s) for s in self.stages)
        if remaining:
            raise KeyError(
                f"pack_state got values for unknown stages "
                f"{sorted(remaining)}; graph stages: {self.stage_names()}"
            )
        return packed, clock

    def unpack_state(
        self, state: Tuple[Tuple[Any, ...], Array]
    ) -> Tuple[Dict[str, Any], Array]:
        """Named per-stage states (stateful stages only) + the clock."""
        states, clock = state
        out: Dict[str, Any] = {}

        def unpack(stage, st) -> None:
            if isinstance(stage, Gated):
                for s, inner in zip(stage.stages, st):
                    unpack(s, inner)
            elif st is not None:
                out[stage.name] = st

        for stage, st in zip(self.stages, states):
            unpack(stage, st)
        return out, clock

    def stage_names(self) -> Tuple[str, ...]:
        names = []

        def walk(stage):
            if isinstance(stage, Gated):
                for s in stage.stages:
                    walk(s)
            else:
                names.append(stage.name)

        for s in self.stages:
            walk(s)
        return tuple(names)

    # -- execution -----------------------------------------------------------

    def step_frame(
        self,
        state: Tuple[Tuple[Any, ...], Array],
        frame: Array,
        pose: Array,
        gaze: Array,
        depth: Optional[Array] = None,
    ) -> Tuple[Tuple[Tuple[Any, ...], Array], Any]:
        """Run every stage on one frame; returns (state, frame stats)."""
        states, t = state
        ctx = FrameCtx(
            frame=frame,
            pose=pose,
            gaze=gaze,
            depth=depth,
            t=t,
            process=jnp.ones((), bool),
            stats={},
        )
        out = []
        for stage, st in zip(self.stages, states):
            st, ctx = _apply_scoped(stage, st, ctx)
            out.append(st)
        stats = self.finalize(ctx) if self.finalize is not None else ctx.stats
        return (tuple(out), self.clock_next(t)), stats

    def scan(
        self,
        state: Tuple[Tuple[Any, ...], Array],
        frames: Array,
        poses: Array,
        gazes: Array,
        depth: Optional[Array] = None,
    ) -> Tuple[Tuple[Tuple[Any, ...], Array], Any]:
        """``lax.scan`` the graph over a chunk of frames (the chunked-
        ingest primitive: the carry is the full graph state)."""

        def body(carry, xs):
            frame, pose, gaze, dgt = xs
            return self.step_frame(carry, frame, pose, gaze, dgt)

        return jax.lax.scan(body, state, (frames, poses, gazes, depth))
