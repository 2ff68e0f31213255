"""EPIC streaming compressor — the full algorithm of paper Figure 3 (c).

Processes an egocentric video stream frame-by-frame (``jax.lax.scan``):

  Frame Bypass Check (light-gray steps 1-3)
      -> [bypassed: nothing else happens]
      -> depth estimation (once per processed frame; crops cached per entry)
      -> HIR saliency (SRD)
      -> TSRC against the DC buffer (dark-gray steps 1-3)

The per-frame body is a **stage graph** (:mod:`repro.api.stages`):
:func:`build_epic_graph` composes the registered ``bypass`` /
``depth`` / ``saliency`` / ``tsrc`` stages, with the three heavy stages
gated behind the bypass check exactly as the paper's figure draws them.
``process_frame`` / ``scan_frames`` / ``compress_stream`` are thin
adapters keeping the public ``EPICState`` / ``FrameStats`` contract —
bit-identical to the pre-stage-graph pipeline (goldens in
``tests/test_stages.py``).

The whole pipeline is a pure function of (stream, models, config): it can be
jit'ed, vmapped over a *batch of streams* (the datacenter deployment mode —
one TPU pod ingesting thousands of glasses streams), and differentiated
through where meaningful.

Oracle modes for ablations (paper Section 5 studies the int8/64x64 depth
design): ground-truth depth maps and/or saliency can be supplied to isolate
the contribution of each learned module.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api import registry as _registry
from repro.api.stages import Gated, StageGraph
from repro.core import dc_buffer as dcb
from repro.core import depth as depth_mod
from repro.core import frame_bypass
from repro.core import geometry as geo
from repro.core import tsrc as tsrc_mod

Array = jax.Array


class _EPICConfig(NamedTuple):
    frame_hw: Tuple[int, int] = (128, 128)
    patch: int = 16
    capacity: int = 192
    # TSRC thresholds
    tau: float = 0.08
    o_min: float = 0.5
    c_min: float = 0.6
    window: int = 32
    backend: str = "ref"
    prefilter_k: int = 0  # 0 = dense TRD; K > 0 = sparse top-K candidates
    patch_k: int = 0  # 0 = dense patch axis; P_k > 0 = salient compaction
    # Frame bypass
    gamma: float = 0.02
    theta: int = 30
    # DC buffer retention
    w_popularity: float = 1.0
    w_recency: float = 0.1
    # Camera: focal length as a fraction of frame width
    focal_frac: float = 0.8

    @property
    def grid(self) -> int:
        g = self.frame_hw[0] // self.patch
        assert self.frame_hw[0] == self.frame_hw[1], "square frames assumed"
        return g

    @property
    def n_patches(self) -> int:
        return self.grid * self.grid

    def intrinsics(self) -> geo.Intrinsics:
        h, w = self.frame_hw
        return geo.Intrinsics.create(self.focal_frac * w, w / 2.0, h / 2.0)

    def buffer_config(self) -> dcb.DCBufferConfig:
        return dcb.DCBufferConfig(
            capacity=self.capacity,
            patch=self.patch,
            w_popularity=self.w_popularity,
            w_recency=self.w_recency,
        )

    def tsrc_config(self) -> tsrc_mod.TSRCConfig:
        return tsrc_mod.TSRCConfig(
            tau=self.tau,
            o_min=self.o_min,
            c_min=self.c_min,
            window=self.window,
            backend=self.backend,
            prefilter_k=self.prefilter_k,
            patch_k=self.patch_k,
        )

    def bypass_config(self) -> frame_bypass.BypassConfig:
        return frame_bypass.BypassConfig(gamma=self.gamma, theta=self.theta)


class EPICConfig(_registry.BackendValidatedConfig, _EPICConfig):
    """EPIC pipeline configuration (see field comments above).

    Construction (and ``_replace``) fails fast on an unregistered
    ``backend`` (the error lists the available reproject-match registry
    keys) or a negative ``prefilter_k`` / ``patch_k`` — instead of
    surfacing deep inside the jitted scan.  ``prefilter_k > 0`` selects
    the two-phase sparse TRD path; ``patch_k > 0`` additionally compacts
    the patch axis of the match algebra (see
    :class:`repro.core.tsrc.TSRCConfig`).
    """

    __slots__ = ()


class EPICModels(NamedTuple):
    depth_params: Any = None  # None -> ground-truth depth oracle mode
    hir_params: Any = None  # None -> all-salient (pure temporal mode)


class EPICState(NamedTuple):
    bypass: frame_bypass.BypassState
    buf: dcb.DCBuffer
    t: Array  # frame index (float32 timestamp)


class FrameStats(NamedTuple):
    processed: Array  # bool — passed the bypass gate
    bypass_diff: Array
    n_salient: Array
    n_matched: Array
    n_inserted: Array
    n_bbox_checks: Array
    n_full_checks: Array
    buffer_valid: Array
    n_prefilter_overflow: Array  # sparse-TRD top-K truncations (0 dense)
    n_patch_overflow: Array  # patch-compaction truncations (0 dense)
    n_patch_checked: Array  # compacted patch slots gathered (0 dense)


def init_state(cfg: EPICConfig) -> EPICState:
    return EPICState(
        bypass=frame_bypass.init(cfg.frame_hw),
        buf=dcb.init(cfg.buffer_config()),
        t=jnp.zeros((), jnp.float32),
    )


def _zero_tsrc_stats(buf: dcb.DCBuffer) -> tsrc_mod.TSRCStats:
    z = jnp.zeros((), jnp.int32)
    return tsrc_mod.TSRCStats(z, z, z, z, z, dcb.count_valid(buf), z, z, z)


# Memoized graph construction: eager per-frame callers (process_frame
# outside jit, REPL exploration) used to rebuild the stage graph — six
# registry lookups + stage construction — on *every* frame.  Keyed on
# ``(cfg, id(models))`` identity with the models object pinned in the
# value so a recycled id can never alias a dead entry; bounded LRU so
# config sweeps don't grow it without limit.  Graphs are stateless
# composition objects (pure functions of cfg + models), so sharing one
# instance across calls is observationally identical.
_GRAPH_CACHE: "OrderedDict[Any, Tuple[EPICModels, StageGraph]]" = (
    OrderedDict()
)
_GRAPH_CACHE_MAX = 32


def build_epic_graph(
    cfg: EPICConfig, models: EPICModels = EPICModels()
) -> StageGraph:
    """Compose EPIC's per-frame pipeline as a stage graph (Figure 3c).

    ``bypass`` runs unconditionally and writes the gate; ``depth`` →
    ``saliency`` → ``tsrc`` are gated behind it under one ``lax.cond``
    (bypassed frames execute none of their compute).  Stages are
    constructed through the registry, so alternative implementations
    slot in by name; the graph state flattens to exactly the
    :class:`EPICState` leaves ``(bypass, buf, t)``.

    Construction is memoized on ``(cfg, models)`` identity, so per-frame
    eager callers pay it once per configuration, not once per frame.
    Inside an active jit/vmap trace the cache is bypassed both ways:
    stage construction stages array constants (omnistaging), so a graph
    built under one trace must neither be stored (its tracers would leak
    into later traces) nor served from an eager build into a trace
    context where cached eager constants are fine — the latter is safe,
    so reads are allowed; only writes are gated.
    """
    key = (cfg, id(models))
    hit = _GRAPH_CACHE.get(key)
    if hit is not None and hit[0] is models:
        _GRAPH_CACHE.move_to_end(key)
        return hit[1]
    graph = _build_epic_graph(cfg, models)
    if _no_active_trace():
        _GRAPH_CACHE[key] = (models, graph)
        while len(_GRAPH_CACHE) > _GRAPH_CACHE_MAX:
            _GRAPH_CACHE.popitem(last=False)
    return graph


def _no_active_trace() -> bool:
    """True when no jax trace would stage the graph's array constants.

    Under an active jit trace every primitive bind is staged out, so a
    freshly built array is a ``Tracer``; outside one it is concrete.
    """
    return not isinstance(jnp.zeros(()), jax.core.Tracer)


def _build_epic_graph(cfg: EPICConfig, models: EPICModels) -> StageGraph:
    make = _registry.make_stage
    gated_stages = [
        make("depth", params=models.depth_params),
        make(
            "saliency",
            params=models.hir_params,
            grid=cfg.grid,
            frame_hw=cfg.frame_hw,
        ),
        make(
            "tsrc",
            buf_cfg=cfg.buffer_config(),
            tsrc_cfg=cfg.tsrc_config(),
            intr=cfg.intrinsics(),
        ),
    ]
    tsrc_idx = next(
        i for i, s in enumerate(gated_stages) if s.name == "tsrc"
    )
    gated = Gated(
        gated_stages,
        # A bypassed frame leaves the buffer untouched and reports the
        # zero TSRC counters (buffer occupancy passes through).
        skip_stats=lambda states, ctx: {
            "tsrc": _zero_tsrc_stats(states[tsrc_idx])
        },
    )

    def finalize(ctx) -> FrameStats:
        b = ctx.stats["bypass"]
        t = ctx.stats["tsrc"]
        return FrameStats(
            processed=b.processed,
            bypass_diff=b.diff,
            n_salient=t.n_salient,
            n_matched=t.n_matched,
            n_inserted=t.n_inserted,
            n_bbox_checks=t.n_bbox_checks,
            n_full_checks=t.n_full_checks,
            buffer_valid=t.buffer_valid,
            n_prefilter_overflow=t.n_prefilter_overflow,
            n_patch_overflow=t.n_patch_overflow,
            n_patch_checked=t.n_patch_checked,
        )

    return StageGraph(
        [
            make("bypass", cfg=cfg.bypass_config(), frame_hw=cfg.frame_hw),
            gated,
        ],
        finalize=finalize,
    )


def _to_graph_state(graph: StageGraph, state: EPICState):
    return graph.pack_state({"bypass": state.bypass, "tsrc": state.buf},
                            state.t)


def _from_graph_state(graph: StageGraph, gstate) -> EPICState:
    named, t = graph.unpack_state(gstate)
    return EPICState(bypass=named["bypass"], buf=named["tsrc"], t=t)


def process_frame(
    state: EPICState,
    frame: Array,
    pose: Array,
    gaze: Array,
    depth_gt: Optional[Array],
    models: EPICModels,
    cfg: EPICConfig,
) -> Tuple[EPICState, FrameStats]:
    """Run the full EPIC algorithm on a single frame (graph adapter)."""
    graph = build_epic_graph(cfg, models)
    gstate, stats = graph.step_frame(
        _to_graph_state(graph, state), frame, pose, gaze, depth_gt
    )
    return _from_graph_state(graph, gstate), stats


def scan_frames(
    state: EPICState,
    frames: Array,  # (T, H, W, 3)
    poses: Array,  # (T, 4, 4)
    gazes: Array,  # (T, 2)
    depth_gt: Optional[Array],  # (T, H, W) oracle depth, or None
    models: EPICModels,
    cfg: EPICConfig,
) -> Tuple[EPICState, FrameStats]:
    """Scan the EPIC algorithm over a chunk of frames from ``state``.

    This is the chunked-ingest primitive: the carry is the full
    :class:`EPICState`, so feeding a stream in arbitrary chunk sizes is
    bit-identical to one big scan — unbounded streams ingest in bounded
    memory (see ``repro.api.EPICCompressor``).
    """
    if models.depth_params is None and depth_gt is None:
        raise ValueError("need depth_gt when no depth model is given")
    graph = build_epic_graph(cfg, models)
    gstate, stats = graph.scan(
        _to_graph_state(graph, state), frames, poses, gazes, depth_gt
    )
    return _from_graph_state(graph, gstate), stats


def compress_stream(
    frames: Array,  # (T, H, W, 3)
    poses: Array,  # (T, 4, 4)
    gazes: Array,  # (T, 2)
    cfg: EPICConfig,
    models: EPICModels = EPICModels(),
    depth_gt: Optional[Array] = None,  # (T, H, W) oracle depth
) -> Tuple[EPICState, FrameStats]:
    """Compress a full stream. Returns final state + per-frame stat arrays.

    .. deprecated::
        One-shot convenience shim kept for backward compatibility; it
        requires the whole video materialized up front.  New code should
        use the session API — ``repro.api.EPICCompressor`` — which
        ingests :class:`repro.api.SensorChunk` chunks incrementally and
        produces bit-identical results.
    """
    return scan_frames(
        init_state(cfg), frames, poses, gazes, depth_gt, models, cfg
    )


# ---------------------------------------------------------------------------
# Energy-model bridge.
# ---------------------------------------------------------------------------


def stream_counters(cfg: EPICConfig, stats: FrameStats, *, int8_depth=True):
    """Convert scan stats into `energy.StreamCounters` for the cost model.

    With ``cfg.prefilter_k > 0`` the ``n_full_checks`` feeding the
    energy model is the *real* per-frame candidate count of the sparse
    TRD path — the compute performed and the energy charged finally
    agree (dense runs keep the ASIC-schedule estimate, which coincides
    whenever no top-K truncation would occur).

    All per-field reductions transfer in a single ``jax.device_get``
    (one host sync) rather than one blocking ``int(...)`` per counter.
    One-stream adapter over :func:`pool_stream_counters` — the byte
    accounting lives in exactly one place.
    """
    return pool_stream_counters(
        cfg, jax.tree.map(lambda x: x[None], stats)
    )[0]


def pool_stream_counters(cfg: EPICConfig, stats: FrameStats, *,
                         streams=None):
    """Per-stream ``energy.StreamCounters`` over a pooled stats pytree.

    ``stats`` leaves carry leading ``(n_streams, T)`` axes (a
    ``StreamPool``/``SlottedPool`` result).  Same numbers as calling
    :func:`stream_counters` per stream — the reductions commute with
    the leading-axis slice — but the whole pool transfers in a
    **single** ``jax.device_get`` instead of one blocking sync per
    stream.  ``streams`` optionally selects a subset of indices.
    Re-exported as ``repro.serve.pool_stream_counters`` for the
    serving-telemetry path.
    """
    from repro.core import energy
    from repro.core import retained as ret

    h, w = cfg.frame_hw
    t = int(stats.processed.shape[1])
    n_proc, full_checks, bbox_checks, inserted, final_valid, pair_reads = (
        jax.device_get(
            (
                jnp.sum(stats.processed.astype(jnp.int32), axis=1),
                jnp.sum(stats.n_full_checks, axis=1),
                jnp.sum(stats.n_bbox_checks, axis=1),
                jnp.sum(stats.n_inserted, axis=1),
                stats.buffer_valid[:, -1],
                # Patch-compacted association gathers: per frame, each of
                # the n_full_checks candidates' bbox rows is read against
                # each compacted patch slot.  n_patch_checked is 0 when
                # no compaction ran, so dense runs charge exactly what
                # they did before (their association is in-engine work,
                # not DC traffic).
                jnp.sum(stats.n_full_checks * stats.n_patch_checked,
                        axis=1),
            )
        )
    )
    patch_bytes = ret.patch_rgb_bytes(cfg.patch)
    entry_bytes = ret.dc_entry_bytes(cfg.patch)
    if streams is None:
        streams = range(stats.processed.shape[0])
    return [
        energy.StreamCounters(
            n_frames=t,
            frame_px=h * w,
            n_processed=int(n_proc[i]),
            depth_macs=depth_mod_macs() * int(n_proc[i]),
            hir_macs=hir_macs() * int(n_proc[i]),
            n_bbox_checks=int(bbox_checks[i]),
            n_full_checks=int(full_checks[i]),
            patch_px=cfg.patch * cfg.patch,
            stored_bytes=int(final_valid[i]) * entry_bytes,
            dc_traffic_bytes=(
                int(full_checks[i]) * patch_bytes
                + int(inserted[i]) * entry_bytes
                + int(pair_reads[i]) * ret.bbox_row_bytes()
            ),
        )
        for i in streams
    ]


def depth_mod_macs() -> int:
    """Analytic MAC count of FastDepth-lite on a 64x64 input."""
    macs = 0
    res = 64
    for _, kind, cin, cout, stride in depth_mod._ENCODER:
        res //= stride
        if kind == "conv":
            macs += res * res * 9 * cin * cout
        else:
            macs += res * res * (9 * cin + cin * cout)
    for _, kind, cin, cout, _ in depth_mod._DECODER:
        res *= 2
        macs += res * res * (9 * cin + cin * cout)
    macs += res * res * 9 * 16 * 1  # head
    return macs


def hir_macs() -> int:
    """Analytic MAC count of the 3-layer HIR CNN on a 64x64 input."""
    return 32 * 32 * 9 * 4 * 16 + 16 * 16 * 9 * 16 * 32 + 16 * 16 * 9 * 32 * 1
