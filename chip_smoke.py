"""Run the EPIC serving path once on a TPU and check what it serves.

Usage, from the repository root:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the stream-mesh server on four chips

One chip: 8 synthetic glasses streams at 1024x1024 (the ~1 Mpx sensor of
``benchmarks/energy_model.py``; patch 16, DC-buffer capacity 192, window
32) enter through the wire codec over ``Loopback`` into an
``IngestServer`` in front of a ``StreamServer`` (8 slots, 10-frame chunks,
adaptive K over the ladder 8/16/24/48).  Each stream sends 3 chunks.
Mid-run the server is checkpointed while it keeps serving; the
checkpoint is restored into a fresh server that serves the rest.  The
same streams are served once more with each compiled Pallas backend
(``fused``, ``pallas``, ``pallas_tiled``), whose lowered step must carry
the Mosaic kernel.  Every served stream is compared with a solo
``EPICCompressor`` session run on the host CPU device in this process.

The served configuration runs neither learned model: depth comes with
the sensor's depth track, and every patch is salient, because the HIR
CNN's 16x16 logit map cannot pool onto the 64x64 patch grid of patch 16
at 1024x1024.  So the two CNNs those stages call — the HIR saliency CNN
and the depth CNN, with weights drawn from ``--seed`` — run on their own
over every served frame, on the chip and on the host CPU, and are
compared.

Four chips: only the sharded path a four-chip host runs — the flat
``StreamServer`` on ``make_stream_mesh(4)``, checkpointed and restored
onto the mesh — compared with the same streams on one chip.

Tolerance: none for serving.  Retained patches (pixels, timestamps,
origins, validity, saliency, popularity, last use) and the served
counters (frames, processed, inserted, buffer occupancy, K trajectory)
must be bitwise equal to the reference.  The learned CNNs: saliency
logits within ``LOGIT_ATOL`` and depth within ``DEPTH_RTOL`` (relative)
of the CPU's.

Everything is generated from ``--seed``.  The last line of standard
output is one JSON object: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  Without a TPU, or when any phase raises
or any comparison fails, it is ``{"ok": false, ...}`` and the exit code
is 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    import jax
    import numpy as np

    from repro.api import EPICCompressor, SensorChunk
    from repro.core import depth as depth_mod
    from repro.core import hir
    from repro.core.pipeline import EPICConfig
    from repro.data import synthetic
    from repro.launch.mesh import make_stream_mesh
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serve import ServerConfig, StreamServer
    from repro.serve.checkpoint import ServeCheckpointer
    from repro.wire import codec
    from repro.wire.server import IngestServer, Loopback
except ImportError as e:  # a checkout without the program
    _IMPORT_ERROR: Optional[ImportError] = e
else:
    _IMPORT_ERROR = None

PATCH = 16
WINDOW = 32
PALLAS_BACKENDS = ("fused", "pallas", "pallas_tiled")
HIR_GRID = 16  # the HIR CNN's own logit map
# The CNNs run at f32 (HIGHEST) precision on both sides.  On a TPU v5e
# the depth CNN still departs from the CPU's by up to 2.3e-4 relative on
# this data (the HIR logits by 3.5e-6); a conv left at the TPU's default
# precision rounds each operand to bf16, up to 2^-9 = 2e-3 relative.
LOGIT_ATOL = 1e-4
DEPTH_RTOL = 1e-3


class Size(NamedTuple):
    """What one run serves."""

    frame: int = 1024  # square frames, the ~1 Mpx glasses sensor
    capacity: int = 192  # DC-buffer entries per stream, the paper's
    streams: int = 8  # = server slots
    chunk_frames: int = 10
    chunks: int = 3  # per stream
    k_ladder: Tuple[int, ...] = (8, 16, 24, 48)

    @property
    def frames(self) -> int:
        return self.chunk_frames * self.chunks


FULL = Size()

# Retained-patch fields compared bitwise (RetainedPatches order).
EXPORT_FIELDS = (
    "rgb", "t", "origin", "valid", "saliency", "popularity", "t_last"
)
COUNTER_FIELDS = (
    "n_frames", "n_processed", "n_inserted", "buffer_valid", "k_trajectory"
)


class SmokeError(RuntimeError):
    """A phase's result is wrong (as opposed to a phase that raised)."""


class StreamResult(NamedTuple):
    export: Dict[str, np.ndarray]  # retained patches, host copies
    counters: Dict[str, Any]  # served counters


class ServeRun(NamedTuple):
    results: List[StreamResult]  # after the last chunk, uninterrupted
    restored: Optional[List[StreamResult]]  # the restored server's
    frames_served: int
    retraces: int  # step variants compiled more than once
    compile_s: float  # backend compile seconds while serving
    ckpt_bytes: int
    n_devices: int  # devices the slot state lives on
    n_devices_restored: int


class CompileClock:
    """Sums JAX's backend-compile durations on this thread while it is
    open (the CPU reference compiles on another).

    A persistent-cache hit is recorded under the same event, as the
    (short) time it took to load the executable.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.thread = threading.get_ident()

    def _listen(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT and threading.get_ident() == self.thread:
            self.seconds += duration

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)


def device_info() -> Dict[str, Any]:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def check_device(chips: int) -> Dict[str, Any]:
    """The default device must be a TPU, with ``chips`` of them."""
    info = device_info()
    if info["platform"] != "tpu":
        raise SmokeError(
            f"the default JAX device is {info['platform']!r}, not a TPU"
        )
    if info["count"] < chips:
        raise SmokeError(f"{chips} chips wanted, JAX sees {info['count']}")
    return info


def epic_config(size: Size, **kw) -> EPICConfig:
    return EPICConfig(
        frame_hw=(size.frame, size.frame), patch=PATCH,
        capacity=size.capacity, window=WINDOW, **kw,
    )


def server_config(size: Size) -> ServerConfig:
    return ServerConfig(
        capacity=size.streams, chunk_frames=size.chunk_frames,
        k_ladder=size.k_ladder,
    )


def make_streams(seed: int, size: Size) -> List[SensorChunk]:
    """Synthetic egocentric streams (frames, poses, gazes, oracle depth)
    as host arrays, rendered on the default device."""
    cfg = synthetic.StreamConfig(n_frames=size.frames, hw=(size.frame,) * 2)
    render = jax.jit(synthetic.generate_stream, static_argnums=1)
    streams = []
    for key in jax.random.split(jax.random.PRNGKey(seed), size.streams):
        s, _ = render(key, cfg)
        streams.append(
            SensorChunk(*jax.device_get((s.frames, s.poses, s.gazes, s.depth)))
        )
    return streams


def _chunks(stream: SensorChunk, chunk_frames: int) -> List[SensorChunk]:
    n = stream.frames.shape[0] // chunk_frames
    return [
        SensorChunk(*(f[i * chunk_frames:(i + 1) * chunk_frames] for f in stream))
        for i in range(n)
    ]


def _export(retained) -> Dict[str, np.ndarray]:
    host = jax.device_get(retained)
    return {f: np.asarray(getattr(host, f)) for f in EXPORT_FIELDS}


def reference(
    cfg: EPICConfig, size: Size, streams: List[SensorChunk]
) -> List[StreamResult]:
    """Each stream as a solo adaptive-K session on the host CPU device."""
    cpu = jax.devices("cpu")[0]
    out = []
    with jax.default_device(cpu):
        for stream in streams:
            comp = EPICCompressor(cfg, k_ladder=size.k_ladder)
            state = comp.init()
            processed = inserted = 0
            valid = None
            for chunk in _chunks(stream, size.chunk_frames):
                state, stats = comp.step(state, chunk)
                st = jax.device_get(stats)
                processed += int(np.sum(st.processed))
                inserted += int(np.sum(st.n_inserted))
                valid = int(st.buffer_valid[-1])
            out.append(
                StreamResult(
                    _export(comp.export(state)),
                    {
                        "n_frames": int(stream.frames.shape[0]),
                        "n_processed": processed,
                        "n_inserted": inserted,
                        "buffer_valid": valid,
                        "k_trajectory": list(comp.k_trajectory),
                    },
                )
            )
    return out


def _expect_ack(reply) -> None:
    if not reply.ok:
        raise SmokeError(
            f"stream {reply.stream_id}: {reply.status_name} "
            f"({codec.STATUS_REASONS[reply.status]})"
        )


def _send_chunk(link: Loopback, sid: int, seq: int, chunk: SensorChunk) -> None:
    _expect_ack(
        link.send(
            codec.encode_chunk(
                chunk, stream_id=sid, seq=seq, timestamp_ns=time.monotonic_ns()
            )
        )
    )


def _drain(ingest: IngestServer) -> None:
    """Tick until a tick finds nothing queued."""
    while ingest.tick():
        pass


def _results(server: StreamServer, n_streams: int) -> List[StreamResult]:
    out = []
    for sid in range(n_streams):
        tele = server.telemetry(sid)
        out.append(
            StreamResult(
                _export(server.export(sid)),
                {
                    "n_frames": tele.n_frames,
                    "n_processed": tele.n_processed,
                    "n_inserted": tele.n_inserted,
                    "buffer_valid": tele.buffer_valid,
                    "k_trajectory": list(tele.k_trajectory),
                },
            )
        )
    return out


def _state_devices(server: StreamServer) -> int:
    return len(server.pool.states.active.sharding.device_set)


def _retraces(server: StreamServer) -> int:
    return sum(n - 1 for n in server.step_cache_sizes().values())


def _dir_bytes(path: str) -> int:
    return sum(
        os.path.getsize(os.path.join(d, f))
        for d, _, files in os.walk(path)
        for f in files
    )


def serve(
    comp: EPICCompressor,
    scfg: ServerConfig,
    streams: List[SensorChunk],
    *,
    mesh=None,
    checkpoint_dir: Optional[str] = None,
) -> ServeRun:
    """Serve every stream through the wire into a ``StreamServer``.

    With ``checkpoint_dir`` the server is checkpointed (asynchronously,
    while it goes on serving) once half the chunks are in; the
    checkpoint is then restored into a fresh server — on the same mesh —
    which serves the remaining chunks.
    """
    chunks = [_chunks(s, scfg.chunk_frames) for s in streams]
    n_chunks = len(chunks[0])
    mid = n_chunks // 2
    with CompileClock() as clock:
        server = StreamServer(comp, scfg, mesh=mesh)
        ingest = IngestServer(server)
        link = Loopback(ingest)
        for sid in range(len(streams)):
            _expect_ack(link.send(codec.encode_control(codec.OP_OPEN, sid)))
        ckpt = None
        for c in range(n_chunks):
            if checkpoint_dir is not None and c == mid:
                ckpt = ServeCheckpointer(checkpoint_dir, server, ingest=ingest)
                ckpt.save_now()
            for sid, sc in enumerate(chunks):
                _send_chunk(link, sid, c, sc[c])
            _drain(ingest)
        results = _results(server, len(streams))
        retraces = _retraces(server)
        frames = server.frames_served

        restored = None
        ckpt_bytes = 0
        n_dev_restored = 0
        if ckpt is not None:
            ckpt.wait()
            ckpt_bytes = _dir_bytes(checkpoint_dir)
            target = None if mesh is None else StreamServer(comp, scfg, mesh=mesh)
            back = ckpt.restore(comp, server=target, with_ingest=True)
            link_b = Loopback(back.ingest)
            for c in range(mid, n_chunks):
                for sid, sc in enumerate(chunks):
                    _send_chunk(link_b, sid, c, sc[c])
                _drain(back.ingest)
            restored = _results(back.server, len(streams))
            retraces += _retraces(back.server)
            n_dev_restored = _state_devices(back.server)
    return ServeRun(
        results, restored, frames, retraces, clock.seconds, ckpt_bytes,
        _state_devices(server), n_dev_restored,
    )


def compare(
    got: List[StreamResult], want: List[StreamResult], what: str
) -> Tuple[List[str], bool]:
    """Bitwise comparison: one line per stream, and whether all agree."""
    if len(got) != len(want):
        return [f"{what}: {len(got)} streams vs {len(want)}"], False
    lines, ok = [], True
    for sid, (g, w) in enumerate(zip(got, want)):
        diffs = {}
        for f in EXPORT_FIELDS:
            a, b = g.export[f], w.export[f]
            if a.shape != b.shape or a.dtype != b.dtype:
                diffs[f] = f"shape/dtype {a.shape}{a.dtype} vs {b.shape}{b.dtype}"
            elif not np.array_equal(a, b):
                n = int(np.sum(a != b))
                d = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
                diffs[f] = f"{n} elements differ, max |d| {d}"
        for f in COUNTER_FIELDS:
            if g.counters[f] != w.counters[f]:
                diffs[f] = f"{g.counters[f]} vs {w.counters[f]}"
        if diffs:
            ok = False
            lines.append(f"{what} stream {sid}: MISMATCH {diffs}")
        else:
            lines.append(
                f"{what} stream {sid}: bitwise equal "
                f"({int(np.sum(g.export['valid']))} retained patches, "
                f"k {g.counters['k_trajectory']})"
            )
    return lines, ok


class Verdict:
    """Failed checks, collected so that one run reports all of them."""

    def __init__(self):
        self.failed: List[str] = []

    def expect(self, ok: bool, what: str) -> None:
        if not ok:
            _say(f"FAILED: {what}")
            self.failed.append(what)

    def compare(self, got, want, what: str, how=compare) -> None:
        lines, ok = how(got, want, what)
        for line in lines:
            _say(line)
        self.expect(ok, what)

    def run(self, tag: str, run: "ServeRun", frames: int, devices: int) -> None:
        _say(
            f"{tag}: {run.frames_served} frames served, compile "
            f"{run.compile_s:.1f} s, retraces after warmup {run.retraces}, "
            f"slot state on {run.n_devices} device(s)"
        )
        if run.restored is not None:
            _say(
                f"{tag}: checkpoint {run.ckpt_bytes} bytes, restored slot "
                f"state on {run.n_devices_restored} device(s)"
            )
        self.expect(run.retraces == 0, f"{tag}: {run.retraces} retraces after warmup")
        self.expect(
            run.frames_served == frames,
            f"{tag}: served {run.frames_served} of {frames} frames",
        )
        self.expect(
            run.n_devices == devices
            and (run.restored is None or run.n_devices_restored == devices),
            f"{tag}: slot state on {run.n_devices}/{run.n_devices_restored} "
            f"devices, want {devices}",
        )

    def close(self) -> None:
        if self.failed:
            raise SmokeError("failed: " + "; ".join(self.failed))


def has_kernel(cfg: EPICConfig, size: Size, stream: SensorChunk) -> bool:
    """The step lowered for the default device carries a Mosaic kernel
    (``tpu_custom_call``), not the Pallas interpreter."""
    comp = EPICCompressor(cfg._replace(prefilter_k=size.k_ladder[0]))
    chunk = _chunks(stream, size.chunk_frames)[0]
    return "tpu_custom_call" in jax.jit(comp.step).lower(comp.init(), chunk).as_text()


def learned_params(seed: int):
    """HIR and depth CNN weights drawn from ``seed``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return (
        hir.init_params(jax.random.fold_in(key, 0)),
        depth_mod.init_params(jax.random.fold_in(key, 1)),
    )


def _learned_frame(params, rgb, gaze):
    """What the saliency and depth stages compute for one frame: the HIR
    logits on its own grid, and the depth model's full-resolution map."""
    hir_p, depth_p = params
    logits = hir.forward(
        hir_p,
        depth_mod.resize_image(rgb, hir.HIR_INPUT)[None],
        hir.gaze_heatmap(gaze, hir.HIR_INPUT, rgb.shape[:2])[None],
        HIR_GRID,
    )[0]
    return logits, depth_mod.predict_fullres(depth_p, rgb)


def learned_outputs(params, streams: List[SensorChunk], device) -> List[Tuple]:
    """The learned CNNs over every frame of every stream, on ``device``."""
    fn = jax.jit(jax.vmap(_learned_frame, in_axes=(None, 0, 0)))
    params = jax.device_put(params, device)
    return [
        jax.device_get(
            fn(params, *jax.device_put((s.frames, s.gazes), device))
        )
        for s in streams
    ]


def compare_learned(got, want, what: str) -> Tuple[List[str], bool]:
    """Per stream: saliency logits within ``LOGIT_ATOL``, depth within
    ``DEPTH_RTOL``; counts the saliency decisions (logit > 0) that differ."""
    lines, ok = [], len(got) == len(want)
    for sid, ((lg, dg), (lw, dw)) in enumerate(zip(got, want)):
        if lg.shape != lw.shape or dg.shape != dw.shape:
            lines.append(f"{what} stream {sid}: MISMATCH shapes "
                         f"{lg.shape}/{dg.shape} vs {lw.shape}/{dw.shape}")
            ok = False
            continue
        d_logit = float(np.max(np.abs(lg - lw)))
        d_depth = float(np.max(np.abs(dg - dw) / np.abs(dw)))
        flips = int(np.sum((lg > 0) != (lw > 0)))
        good = (
            bool(np.all(np.isfinite(lg)) and np.all(np.isfinite(dg)))
            and d_logit <= LOGIT_ATOL and d_depth <= DEPTH_RTOL
        )
        ok &= good
        lines.append(
            f"{what} stream {sid}: {'within tolerance' if good else 'MISMATCH'}"
            f" (max |d logit| {d_logit:.3g}, saliency decisions differing "
            f"{flips} of {lw.size}, salient {float(np.mean(lw > 0)):.3f}; "
            f"max rel d depth {d_depth:.3g})"
        )
    return lines, ok


def _say(line: str) -> None:
    print(line, flush=True)


class Timer:
    def __enter__(self) -> "Timer":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self.t0


def one_chip(seed: int, size: Size = FULL) -> None:
    """Serve on the default device with ``ref`` (checkpointed and
    restored mid-run) and with each compiled Pallas backend; compare all
    with the CPU reference.  Then run the learned CNNs on both."""
    verdict = Verdict()
    with Timer() as t:
        streams = make_streams(seed, size)
    _say(f"data: {size.streams} streams x {size.frames} frames at "
         f"{size.frame}x{size.frame} ({t.s:.1f} s)")
    cfg = epic_config(size)
    scfg = server_config(size)
    total = size.streams * size.frames

    def timed_reference():
        with Timer() as t:
            ref = reference(cfg, size, streams)
        return ref, t.s

    # The CPU reference runs beside the chip's first serving phase.
    with ThreadPoolExecutor(1) as cpu_thread:
        pending = cpu_thread.submit(timed_reference)
        with Timer() as t, tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            run = serve(EPICCompressor(cfg), scfg, streams, checkpoint_dir=tmp)
        _say(f"serve[ref]: {t.s:.1f} s wall")
        ref, ref_s = pending.result()
    _say(f"reference: solo sessions on the host CPU ({ref_s:.1f} s)")
    verdict.run("serve[ref]", run, total, 1)
    verdict.compare(run.results, ref, "serve[ref] vs cpu reference")
    verdict.compare(run.restored, run.results, "restored vs uninterrupted")

    compile_s = run.compile_s
    for backend in PALLAS_BACKENDS:
        tag = f"serve[{backend}]"
        bcfg = cfg._replace(backend=backend)
        with Timer() as t:
            run_b = serve(EPICCompressor(bcfg), scfg, streams)
        _say(f"{tag}: {t.s:.1f} s wall")
        verdict.run(tag, run_b, total, 1)
        verdict.compare(run_b.results, ref, f"{tag} vs cpu reference")
        kernel = has_kernel(bcfg, size, streams[0])
        _say(f"{backend} step HLO carries tpu_custom_call: {kernel}")
        verdict.expect(kernel, f"the {backend} step is lowered to a Mosaic kernel")
        compile_s += run_b.compile_s
    _say(f"compile seconds (serving phases): {compile_s:.1f}")

    params = learned_params(seed)
    with Timer() as t:
        chip = learned_outputs(params, streams, jax.devices()[0])
    _say(f"learned CNNs on {size.streams * size.frames} frames: {t.s:.1f} s wall")
    verdict.compare(
        chip, learned_outputs(params, streams, jax.devices("cpu")[0]),
        "learned CNNs vs cpu", how=compare_learned,
    )
    verdict.close()


def four_chips(seed: int, size: Size = FULL) -> None:
    """Serve on a four-device stream mesh (checkpointed and restored
    onto the mesh) and on one device; compare the two."""
    verdict = Verdict()
    with Timer() as t:
        streams = make_streams(seed, size)
    _say(f"data: {size.streams} streams x {size.frames} frames at "
         f"{size.frame}x{size.frame} ({t.s:.1f} s)")
    comp = EPICCompressor(epic_config(size))
    scfg = server_config(size)
    total = size.streams * size.frames
    with Timer() as t, tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        run_m = serve(
            comp, scfg, streams, mesh=make_stream_mesh(4), checkpoint_dir=tmp
        )
    _say(f"serve[mesh4]: {t.s:.1f} s wall")
    verdict.run("serve[mesh4]", run_m, total, 4)
    with Timer() as t:
        run_1 = serve(comp, scfg, streams)
    _say(f"serve[1chip]: {t.s:.1f} s wall")
    verdict.run("serve[1chip]", run_1, total, 1)
    verdict.compare(run_m.results, run_1.results, "mesh4 vs one chip")
    verdict.compare(run_m.restored, run_m.results, "mesh4 restored vs uninterrupted")
    _say(f"compile seconds (serving phases): {run_m.compile_s + run_1.compile_s:.1f}")
    verdict.close()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the stream-mesh path, against one chip",
    )
    args = ap.parse_args(argv)
    try:
        if _IMPORT_ERROR is not None:
            raise _IMPORT_ERROR
        info = check_device(args.chips)
        cache = enable_compile_cache()
        _say(
            f"device: platform={info['platform']} kind={info['kind']} "
            f"count={info['count']}"
        )
        _say(f"compile cache: {cache}")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
        _say(f"wall: {time.perf_counter() - t0:.1f} s")
    except Exception as e:  # the boundary: report, and exit non-zero
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}))
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
