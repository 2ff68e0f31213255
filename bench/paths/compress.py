"""The EPIC compressor's served path: what a configuration without a
``"path"`` key runs.

The system under test is the serving path of ``src/repro``: encoded
wire frames go through ``Loopback`` into ``IngestServer.handle_message``
(decode, CRC, queueing), and one serving thread runs
``IngestServer.tick()`` -> ``StreamServer.tick()`` (rung-bucketed slot
pool steps over the EPIC stage graph with the configured reproject-match
kernel, and the tick's one batched readback).  Everything else here is
the yardstick: the clip bank, the wire-time schedule, the reference
comparison of the DC buffers and the path's records for the readers.
A chunk is the unit its ``latency_p50_ms`` is over.

Another path can compose this one: subclass :class:`Path` and hand
:meth:`Path.serve` a :class:`Requests` generator of its own, which runs
beside the chunks from the window's opening to its close.
"""

from __future__ import annotations

import bisect
import math
import queue
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference, render, stats
from bench.harness import LayerInput, say

from repro.api import EPICCompressor, SensorChunk
from repro.core.pipeline import EPICConfig
from repro.obs.trace import FlightRecorder
from repro.serve import ServerConfig, StreamServer
from repro.serve.telemetry import tick_readback
from repro.wire import codec
from repro.wire.server import IngestServer, Loopback

TRACE_SECONDS = 10.0  # longest traced slice, at the end of the window
GRACE_SECONDS = 60.0  # longest wait for a chunk due in the window


# -- the clip bank, encoded once --------------------------------------------------


class Bank(NamedTuple):
    """Pre-encoded wire frames, one per clip chunk.

    Each frame is a ``bytearray`` whose 44-byte header is re-stamped in
    place before a send (stream id, seq, timestamp); the CRC covers only
    the payload, so it is computed once here.
    """

    frames: List[bytearray]
    flags: List[Tuple[int, int, int]]  # (flags, crc, payload nbytes)
    clips: int
    chunks_per_clip: int

    def entry(self, sid: int, seq: int, streams: int) -> int:
        clip = sid % self.clips
        per_clip = math.ceil(streams / self.clips)
        offset = (sid // self.clips) * self.chunks_per_clip // per_clip
        return clip * self.chunks_per_clip + (offset + seq) % self.chunks_per_clip

    def stamp(self, i: int, sid: int, seq: int, ts_ns: int) -> bytearray:
        flags, crc, nbytes = self.flags[i]
        buf = self.frames[i]
        codec.FRAME_HEADER.pack_into(
            buf, 0, codec.DATA_MAGIC, codec.WIRE_VERSION, flags, sid, seq,
            ts_ns, crc, nbytes,
        )
        return buf

    def chunk(self, i: int) -> SensorChunk:
        """The chunk frame ``i`` carries (zero-copy views)."""
        return codec.decode_frame(self.frames[i], verify_crc=False).chunk


def bank_clips(cfg: Dict, mix: Dict) -> int:
    frame_bytes = cfg["frame"] ** 2 * 16  # RGB + depth, float32
    fit = int(mix["bank_bytes"] // (frame_bytes * mix["clip_frames"]))
    return max(1, min(mix["clips_max"], fit))


def make_bank(seed: int, cfg: Dict, mix: Dict) -> Bank:
    """Render the clips on the device, then fetch and encode one clip at
    a time (host memory holds the encoded bank and one clip)."""
    clips = bank_clips(cfg, mix)
    cf = cfg["chunk_frames"]
    per_clip = mix["clip_frames"] // cf
    t = time.monotonic()
    dev = render.render_bank(
        seed, mix["motion"], clips, mix["clip_frames"], cfg["frame"],
        cfg["focal_frac"],
    )
    jax.block_until_ready(dev)
    t_render = time.monotonic() - t
    frames: List[bytearray] = []
    flags: List[Tuple[int, int, int]] = []
    template = None
    t_fetch = 0.0
    with ThreadPoolExecutor(4) as pool:
        for c in range(clips):
            t = time.monotonic()
            host = jax.device_get(tuple(x[c] for x in dev))
            t_fetch += time.monotonic() - t
            chunks = [
                SensorChunk(*(x[j * cf:(j + 1) * cf] for x in host))
                for j in range(per_clip)
            ]
            if template is None:
                template = codec.encode_chunk(chunks[0], stream_id=0, seq=0, timestamp_ns=0)
            for buf, crc in pool.map(lambda ch: _encode(ch, template), chunks):
                frames.append(buf)
                flags.append((codec.FRAME_HEADER.unpack_from(template)[2], crc, len(buf) - codec.DATA_HEADER_NBYTES))
            del host, chunks
    del dev
    if bytes(frames[0][codec.FRAME_HEADER.size:]) != template[codec.FRAME_HEADER.size:]:
        raise RuntimeError("the bank's first frame differs from the codec's encoding")
    say(f"bank: rendered in {t_render:.1f} s, fetched in {t_fetch:.1f} s")
    return Bank(frames, flags, clips, per_clip)


def _encode(chunk: SensorChunk, template: bytes) -> Tuple[bytearray, int]:
    """One data frame laid out as ``codec.encode_chunk`` lays it out
    (``template`` gives the field table, the same for every chunk of
    the bank), with one copy of the payload."""
    fields = [np.ascontiguousarray(f) for f in chunk]
    head = codec.DATA_HEADER_NBYTES
    buf = bytearray(head + sum(f.nbytes for f in fields))
    buf[:head] = template[:head]
    view = memoryview(buf)
    off = head
    for f in fields:
        view[off:off + f.nbytes] = f.reshape(-1).view(np.uint8)
        off += f.nbytes
    return buf, zlib.crc32(view[head:])


# -- the server ----------------------------------------------------------------------


def build_server(cfg: Dict, streams: int) -> Tuple[StreamServer, IngestServer]:
    ecfg = EPICConfig(
        frame_hw=(cfg["frame"], cfg["frame"]), patch=cfg["patch"],
        capacity=cfg["capacity"], tau=cfg["tau"], o_min=cfg["o_min"],
        c_min=cfg["c_min"], window=cfg["window"], backend=cfg["backend"],
        gamma=cfg["gamma"], theta=cfg["theta"],
        w_popularity=cfg["w_popularity"], w_recency=cfg["w_recency"],
        focal_frac=cfg["focal_frac"],
    )
    srv = StreamServer(
        EPICCompressor(ecfg),
        ServerConfig(
            capacity=streams, chunk_frames=cfg["chunk_frames"],
            k_ladder=tuple(cfg["k_ladder"]), queue_depth=cfg["queue_depth"],
            queue_policy=cfg["queue_policy"],
        ),
    )
    return srv, IngestServer(srv)


def warm(srv: StreamServer, cfg: Dict, streams: int) -> None:
    """Compile, before the window, every program the window can run.

    Each rung's full-capacity masked step (with a mask that steps no
    slot, so no state changes), the elementwise combine of two rungs'
    stats, the tick's readback reductions, and the slot-mask scatter for
    every number of streams a rung can hold.
    """
    f, cf = cfg["frame"], cfg["chunk_frames"]
    batch = SensorChunk(
        jnp.zeros((streams, cf, f, f, 3), jnp.float32),
        jnp.zeros((streams, cf, 4, 4), jnp.float32),
        jnp.zeros((streams, cf, 2), jnp.float32),
        jnp.zeros((streams, cf, f, f), jnp.float32),
    )
    none = jnp.zeros((streams,), bool)
    parts = []
    for k in cfg["k_ladder"]:
        parts.append(
            srv.pool.step(batch, mask=none, step_fn=srv._rung_comp(k).step, key=k)
        )
    combined = jax.tree.map(
        lambda a, b: jnp.logical_or(a, b) if a.dtype == bool else a + b,
        parts[0], parts[-1],
    )
    tick_readback([combined])
    sids = srv.live_sessions
    for n in range(1, len(sids) + 1):
        srv._slot_mask(0, sids[:n])
    srv.block_until_ready()


# -- the path's interface ----------------------------------------------------------------


class Path:
    """One cell's run on this path, in the order ``harness.run`` drives it."""

    def __init__(self, cfg: Dict, mix: Dict, *, seed: int):
        """Set-up: the clip bank, the server with every stream open, and
        the warm-up of every program the window runs."""
        self.cfg, self.mix, self.seed = cfg, mix, seed
        streams, cf = mix["streams"], cfg["chunk_frames"]
        t = time.monotonic()
        self.bank = make_bank(seed, cfg, mix)
        say(
            f"bank: {len(self.bank.frames)} chunks of {cf} frames at "
            f"{cfg['frame']}x{cfg['frame']} from {self.bank.clips} clips, "
            f"{sum(len(b) for b in self.bank.frames) / 1e9:.2f} GB encoded "
            f"({time.monotonic() - t:.1f} s)"
        )
        t = time.monotonic()
        self.server, self.ingest = build_server(cfg, streams)
        self.link = Loopback(self.ingest)
        for sid in range(streams):
            reply = self.link.send(codec.encode_control(codec.OP_OPEN, sid))
            if not reply.ok:
                raise RuntimeError(f"OPEN {sid}: {reply.status_name}")
        warm(self.server, cfg, streams)
        say(f"server: {streams} slots built and warmed ({time.monotonic() - t:.1f} s)")

    def serve(self, *, seconds: float, trace: bool, t_start: float, logdir: str,
              extra: Sequence["Requests"] = ()) -> harness.Served:
        try:
            r = _serve(
                self.server, self.ingest, self.link, self.bank, self.cfg, self.mix,
                seed=self.seed, seconds=seconds, trace=trace, t_start=t_start,
                logdir=logdir, extra=extra,
            )
        finally:
            untime_decode()
        self.result = r
        return harness.Served(
            r["window"], r["attempted"], r["failed"], r["e2e"], r["latencies"],
            r.get("traced"), r.get("layer_input"),
        )

    def release(self) -> None:
        """What the program served, for the sample; then drop its state."""
        self.sample = _sample(self.result["sends"], self.seed, self.mix["sample_streams"])
        self.served = {sid: _served(self.server, sid) for sid in self.sample}
        _report_streams(self.server, self.result)
        self.server = self.ingest = self.link = None

    def check(self) -> Dict[str, Dict[str, Any]]:
        return _check(self.cfg, self.bank, self.result, self.sample, self.served)


class Requests:
    """Requests of another kind than chunks, served beside them: a thread
    of their own sends one every ``period`` seconds from the window's
    opening to its close, on the run's monotonic clock and link.

    A subclass's :meth:`send` sends one and appends ``(due, done)`` to
    ``records`` once it is answered; the run drains until every request
    sent has its record.  ``wake`` wakes the serving thread.
    """

    name = "requests"  # its key in ``LayerInput.records``

    def __init__(self, period: float):
        self.period = period
        self.sent = 0
        self.records: List[Tuple[float, float]] = []
        self.error: Optional[BaseException] = None

    def send(self, link: Loopback, due: float, wake: Callable[[], None]) -> None:
        raise NotImplementedError

    def answered(self) -> bool:
        return len(self.records) >= self.sent

    def start(self, link: Loopback, w0: float, w1: float, wake: Callable[[], None]) -> None:
        def loop() -> None:
            try:
                due = w0
                while due <= w1:
                    wait = due - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    self.sent += 1
                    self.send(link, due, wake)
                    due += self.period
            except BaseException as e:  # reported by the main thread
                self.error = e

        self.thread = threading.Thread(target=loop, name=self.name, daemon=True)
        self.thread.start()

    def join(self) -> None:
        self.thread.join()
        if self.error is not None:
            raise self.error


# -- clocks and records ------------------------------------------------------------


class Sends:
    """Every data frame the generator sent: its send interval, stream,
    seq and due time.  The server stamps a chunk's enqueue inside its
    send, so the send whose start is the last at or before that stamp
    is the chunk's."""

    def __init__(self):
        self.t0: List[float] = []
        self.meta: List[Tuple[int, int, float]] = []  # sid, seq, due
        self.t1: List[float] = []
        self.acked: Dict[int, List[int]] = {}  # sid -> acked seqs in order
        self.refused: List[Tuple[int, int, float]] = []

    def find(self, enqueue_ts: float) -> Tuple[int, int, float]:
        i = bisect.bisect_right(self.t0, enqueue_ts) - 1
        return self.meta[i]


class Completions:
    """The server's latency hook: one call per stepped chunk after the
    tick's readback, with the chunk's enqueue stamp."""

    def __init__(self, sends: Sends, chunk_frames: int, on_done: Callable):
        self.sends = sends
        self.chunk_frames = chunk_frames
        self.on_done = on_done
        self.done: Dict[Tuple[int, int], float] = {}
        self.ticks: List[Tuple[float, int]] = []  # readback time, frames

    def observe(self, enqueue_ts: float, pop_ts: float, done: float) -> None:
        sid, seq, _ = self.sends.find(enqueue_ts)
        self.done[(sid, seq)] = done
        if self.ticks and self.ticks[-1][0] == done:
            self.ticks[-1] = (done, self.ticks[-1][1] + self.chunk_frames)
        else:
            self.ticks.append((done, self.chunk_frames))
        self.on_done(sid)


class Stepped(NamedTuple):
    """One stepped chunk, read from the stream's telemetry after its tick."""

    done: float
    sid: int
    k: int
    processed: int


class Serving:
    """The serving thread: waits on the enqueue event, then ticks."""

    def __init__(self, ingest: IngestServer, srv: StreamServer):
        self.ingest = ingest
        self.srv = srv
        self.wake = threading.Event()
        self.stop = threading.Event()
        self.stepped: List[Stepped] = []
        self.error: Optional[BaseException] = None
        self._processed: Dict[int, int] = {}
        self.thread = threading.Thread(target=self._loop, name="serving", daemon=True)

    def _loop(self) -> None:
        try:
            while True:
                self.wake.wait()
                self.wake.clear()
                if self.stop.is_set() and not any(
                    len(q) for q in self.srv._queues.values()
                ):
                    return
                sids = self.ingest.tick()
                now = time.monotonic()
                for sid in sids:
                    tele = self.srv.telemetry(sid)
                    before = self._processed.get(sid, 0)
                    self._processed[sid] = tele.n_processed
                    self.stepped.append(
                        Stepped(now, sid, tele.k_trajectory[-1], tele.n_processed - before)
                    )
        except BaseException as e:  # reported by the main thread
            self.error = e

    def finish(self) -> None:
        self.stop.set()
        self.wake.set()
        self.thread.join()
        if self.error is not None:
            raise self.error


# -- one window ------------------------------------------------------------------------------


def _serve(srv, ingest, link, bank, cfg, mix, *, seed, seconds, trace,
           t_start, logdir, extra=()) -> Dict:
    streams, cf, fps = mix["streams"], cfg["chunk_frames"], cfg["fps"]
    sends = Sends()
    closed = mix["loop"] == "closed"
    ready: "queue.Queue[int]" = queue.Queue()
    comp = Completions(sends, cf, ready.put if closed else (lambda sid: None))
    srv.latency = comp
    recorder = None
    decode_log: List[Tuple[float, float]] = []
    if trace:
        recorder = FlightRecorder(capacity=1 << 16)
        srv.recorder = recorder
        _time_decode(decode_log)
    serving = Serving(ingest, srv)
    stop_sending = threading.Event()
    next_seq = {sid: 0 for sid in range(streams)}
    lateness: List[float] = []

    def send(sid: int, due: float) -> None:
        seq = next_seq[sid]
        next_seq[sid] = seq + 1
        msg = bank.stamp(bank.entry(sid, seq, streams), sid, seq, int(due * 1e9))
        t0 = time.monotonic()
        lateness.append(t0 - due)
        sends.t0.append(t0)
        sends.meta.append((sid, seq, due))
        reply = link.send(msg)
        sends.t1.append(time.monotonic())
        if reply.ok:
            sends.acked.setdefault(sid, []).append(seq)
            serving.wake.set()
        else:
            sends.refused.append((sid, seq, due))

    errors: List[BaseException] = []

    def closed_loop() -> None:
        try:
            for _ in range(mix["outstanding"]):
                for sid in range(streams):
                    send(sid, time.monotonic())
            while True:
                sid = ready.get()
                if sid < 0 or stop_sending.is_set():
                    return
                send(sid, time.monotonic())
        except BaseException as e:
            errors.append(e)

    period = cf / fps
    t_open = time.monotonic() + 0.05

    def due_of(sid: int, seq: int) -> float:
        return t_open + sid * period / streams + (seq * cf + cf - 1) / fps

    def open_loop() -> None:
        try:
            while not stop_sending.is_set():
                sid = min(range(streams), key=lambda s: due_of(s, next_seq[s]))
                due = due_of(sid, next_seq[sid])
                wait = due - time.monotonic()
                if wait > 0 and stop_sending.wait(wait):
                    return
                send(sid, due)
        except BaseException as e:
            errors.append(e)

    gen = threading.Thread(
        target=closed_loop if closed else open_loop, name="generator", daemon=True
    )
    serving.thread.start()
    gen.start()
    # Warm-up: a few ticks of the cell's own traffic.
    warm_ticks = mix["warmup_ticks"]
    deadline = time.monotonic() + 600
    while len(comp.ticks) < warm_ticks and time.monotonic() < deadline:
        _check_threads(errors, serving)
        time.sleep(0.01)
    if len(comp.ticks) < warm_ticks:
        raise RuntimeError("the server completed no warm-up ticks")
    out: Dict[str, Any] = {"sends": sends}
    w0 = time.monotonic()
    setup_s = w0 - t_start
    w1 = w0 + seconds
    for r in extra:
        r.start(link, w0, w1, serving.wake.set)
    if trace:
        # The host's spans are read before the traced slice, the device's
        # operations inside it; the profiler records device operations
        # only, so that the slice serves as the rest of the window does.
        t_trace = w1 - min(TRACE_SECONDS, seconds / 2)
        _sleep_until(t_trace, errors, serving)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        wall_minus_mono = time.time_ns() - time.monotonic_ns()
        _sleep_until(w1, errors, serving)
        jax.profiler.stop_trace()
    _sleep_until(w1, errors, serving)
    out["window"] = (w0, w1)

    # Chunks due in the window: wait (up to a grace) for each to come back.
    if closed:
        due_in = [(sid, seq, due) for sid, seq, due in sends.meta if w0 <= due <= w1]
    else:
        due_in = [
            (sid, seq, due_of(sid, seq))
            for sid in range(streams)
            for seq in range(int((w1 - t_open) * fps / cf) + 2)
            if w0 <= due_of(sid, seq) <= w1
        ]
    refused = {(sid, seq) for sid, seq, _ in sends.refused}
    grace = w1 + GRACE_SECONDS
    while time.monotonic() < grace:
        _check_threads(errors, serving)
        if all((s, q) in comp.done or (s, q) in refused for s, q, _ in due_in):
            break
        time.sleep(0.01)
    stop_sending.set()
    ready.put(-1)
    gen.join()
    for r in extra:
        r.join()
    # Drain: every acknowledged chunk (and every other request) is served
    # before the check.
    drain_until = time.monotonic() + GRACE_SECONDS
    acked = [(s, q) for s, qs in sends.acked.items() for q in qs]
    while time.monotonic() < drain_until and not (
        all(a in comp.done for a in acked) and all(r.answered() for r in extra)
    ):
        serving.wake.set()
        _check_threads(errors, serving)
        time.sleep(0.01)
    serving.finish()
    _check_threads(errors, serving)
    out["unserved"] = sum(a not in comp.done for a in acked)

    rate = stats.snapped_rate(comp.ticks, w0, w1)
    if rate is None:
        raise RuntimeError("fewer than two readbacks completed inside the window")
    # A chunk that never came back (refused, or unfinished when the
    # grace ran out) is censored at the grace: it came back no earlier.
    latencies = [
        comp.done.get((s, q), w1 + GRACE_SECONDS) - due for s, q, due in due_in
    ]
    missing = sum((s, q) not in comp.done for s, q, _ in due_in)
    late = [x for x in lateness if x > 1e-3]
    say(
        f"window: {seconds:.1f} s, {rate[1]} frames in {rate[2]:.3f} s between "
        f"readbacks, {len(due_in)} chunks due, {missing} "
        f"missing, {len(sends.refused)} refused in the run"
    )
    say(
        f"generator lateness: median {np.median(lateness) * 1e3:.2f} ms, "
        f"max {max(lateness) * 1e3:.2f} ms, late sends {len(late)} of {len(lateness)}"
    )
    e2e = {"frames_per_s": rate[0], "setup_s": setup_s}
    if latencies:
        e2e["latency_p95_ms"] = stats.percentile(latencies, 95) * 1e3
        e2e["latency_p50_ms"] = stats.percentile(latencies, 50) * 1e3
    out["e2e"] = e2e
    out["latencies"] = [(due, x) for (_, _, due), x in zip(due_in, latencies)]
    if closed:
        out["attempted"] = sum(w0 <= t0 <= w1 for t0 in sends.t0)
        out["failed"] = sum(w0 <= due <= w1 for _, _, due in sends.refused)
    else:
        out["attempted"] = len(due_in)
        out["failed"] = missing
    in_window = [s for s in serving.stepped if w0 <= s.done <= w1]
    out["bypass"] = (
        sum(s.processed for s in in_window), len(in_window) * cf
    )
    if trace:
        out.update(_read_trace(
            logdir, wall_minus_mono, w0, t_trace, comp, serving, recorder,
            decode_log, sends, cfg, extra,
        ))
    return out


def _read_trace(logdir, wall_minus_mono, w0, t_trace, comp, serving, recorder,
                decode_log, sends, cfg, extra) -> Dict[str, Any]:
    host_spans = [
        ("tick." + name, a, b) for tk in recorder.ticks() for name, a, b in tk["spans"]
    ] + [("wire.send", a, b) for a, b in zip(sends.t0, sends.t1)]
    traced = harness.read_trace(logdir, wall_minus_mono, host_spans)
    p0, p1 = traced.t0, traced.t1
    before, inside = stats.snapped_rate(comp.ticks, w0, t_trace), stats.snapped_rate(comp.ticks, p0, p1)
    say(
        "frames/s before the traced slice "
        + (f"{before[0]:.3f}" if before else "n/a")
        + ", inside it " + (f"{inside[0]:.3f}" if inside else "n/a")
    )
    return {
        "traced": traced,
        "layer_input": LayerInput(
            trace=traced.trace,
            ticks=[
                tk for tk in recorder.ticks()
                if w0 <= tk["t0"] and tk.get("t1", math.inf) <= t_trace
            ],
            decode_s=[d for t0, d in decode_log if w0 <= t0 and t0 + d <= t_trace],
            stepped=[st for st in serving.stepped if p0 <= st.done <= p1],
            cfg=cfg,
            peaks=harness.peaks(jax.devices()[0].device_kind),
            records={r.name: r.records for r in extra},
        ),
    }


def _time_decode(log: List[Tuple[float, float]]) -> None:
    """Time ``codec.decode_message`` for data frames (the ingest path
    looks it up on the module at each call); :func:`untime_decode`
    puts the original back."""
    orig = codec.decode_message

    def timed(buf, **kw):
        t0 = time.monotonic()
        out = orig(buf, **kw)
        if out[0] == "data":
            log.append((t0, time.monotonic() - t0))
        return out

    timed.orig = orig
    codec.decode_message = timed


def untime_decode() -> None:
    codec.decode_message = getattr(codec.decode_message, "orig", codec.decode_message)


def _sleep_until(t: float, errors, serving) -> None:
    while True:
        _check_threads(errors, serving)
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _check_threads(errors, serving) -> None:
    if errors:
        raise errors[0]
    if serving.error is not None:
        raise serving.error


# -- correctness ------------------------------------------------------------------


def _sample(sends: Sends, seed: int, n: int) -> List[int]:
    """The stream with the most acknowledged chunks, and ``n - 1`` more
    drawn from the seed."""
    sids = sorted(sends.acked)
    if not sids:
        return []
    longest = max(sids, key=lambda s: (len(sends.acked[s]), -s))
    others = [s for s in sids if s != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(others), size=min(n - 1, len(others)), replace=False)
    return [longest] + sorted(others[i] for i in pick)


def _served(srv: StreamServer, sid: int) -> reference.Result:
    host = jax.device_get(srv.export(sid))
    tele = srv.telemetry(sid)
    return reference.Result(
        {f: np.asarray(getattr(host, f)) for f in reference.RETAINED},
        {
            "n_frames": tele.n_frames, "n_processed": tele.n_processed,
            "n_inserted": tele.n_inserted, "buffer_valid": tele.buffer_valid,
            "k_trajectory": list(tele.k_trajectory),
        },
    )


def _report_streams(srv: StreamServer, result: Dict) -> None:
    frames = processed = 0
    for sid in srv.live_sessions:
        tele = srv.telemetry(sid)
        frames += tele.n_frames
        processed += tele.n_processed
        traj = list(tele.k_trajectory)
        say(f"stream {sid}: {tele.n_chunks} chunks, K trajectory {_runs(traj)}")
    p, n = result["bypass"]
    say(
        f"bypass gate passed {processed} of {frames} frames in the run "
        f"({100.0 * processed / max(frames, 1):.1f}%), {p} of {n} in the window "
        f"({100.0 * p / max(n, 1):.1f}%)"
    )


def _runs(traj: List[int]) -> str:
    """``8x3 16x40`` for a trajectory of 3 chunks at 8 then 40 at 16."""
    out: List[List[int]] = []
    for k in traj:
        if out and out[-1][0] == k:
            out[-1][1] += 1
        else:
            out.append([k, 1])
    return " ".join(f"{k}x{n}" for k, n in out)


def stream_chunks(bank: Bank, sid: int, seqs: List[int], streams: int):
    for seq in seqs:
        c = bank.chunk(bank.entry(sid, seq, streams))
        yield c.frames, c.poses, c.depth


# Limits of the numbers compared with the reference (PERF.md, section 2,
# gives the readings each was set from).
LIMITS = {
    "unserved_chunks": 0,  # acknowledged chunks never read back: exact
    "counters_off": 0,  # frames, processed, occupancy, K per chunk: exact
    "entries_off_pct": 20.0,  # share of retained entries that differ
    "inserted_gap_ppm": 10.0,  # gap in inserted patches, per million
}


def _check(cfg, bank, result, sample, served) -> Dict[str, Dict[str, Any]]:
    """Each sampled stream against the reference.  Counters are exact;
    the fused kernel's float arithmetic is not the reference's operation
    for operation, so a match decision near a threshold can flip, which
    moves a few retained entries and the count of inserted patches."""
    sends: Sends = result["sends"]
    if not sample:
        raise RuntimeError("no stream had a chunk acknowledged")
    streams = max(sends.acked) + 1
    p = reference.Params.of(cfg)
    t = time.monotonic()
    entries = entries_off = counters_off = gap = inserted = 0
    for sid in sample:
        want = reference.run_stream(
            p, cfg["k_ladder"], list(stream_chunks(bank, sid, sends.acked[sid], streams))
        )
        d = reference.compare(served[sid], want)
        entries += want.retained["valid"].shape[0]
        entries_off += d.entries
        counters_off += d.counters
        gap += d.inserted_gap
        inserted += want.counters["n_inserted"]
        say(
            f"stream {sid} vs reference: "
            + ("; ".join(d.lines) if d.lines else "bitwise equal")
            + f" ({int(np.sum(want.retained['valid']))} retained patches, "
            f"{len(sends.acked[sid])} chunks)"
        )
    say(
        f"reference: {len(sample)} streams in {time.monotonic() - t:.1f} s; "
        f"{entries_off} of {entries} entries differ, inserted gap {gap} of {inserted}"
    )
    values = {
        "unserved_chunks": result["unserved"],
        "counters_off": counters_off,
        "entries_off_pct": 100.0 * entries_off / entries,
        "inserted_gap_ppm": 1e6 * gap / max(inserted, 1),
    }
    return {k: {"value": values[k], "limit": lim} for k, lim in LIMITS.items()}
