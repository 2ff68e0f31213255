"""Ingest-frontier tests (`repro.wire`): codec round-trip properties
(zero-copy, dtype/shape/optional-depth sweep), corrupt/truncated/
wrong-version rejection, loopback ingest -> StreamServer bitwise parity
with in-process sessions (state + k_trajectory), trace record/replay
bitwise parity, seeded loadgen determinism, queue timestamp/policy
semantics, latency histogram math, and the TCP socket path."""

import math
import os
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import pipeline as P
from repro.data import synthetic as SYN
from repro.serve import ChunkQueue, ServerConfig, StreamServer
from repro.wire import codec, trace
from repro.wire.latency import LatencyHistogram, LatencyRecorder
from repro.wire.loadgen import LoadConfig, LoadGen
from repro.wire.server import IngestServer, Loopback, WireClient

from tests._hypothesis_compat import given, settings, strategies as st

FRAME = 64
PATCH = 16
CHUNK = 8


def _ecfg(**kw):
    base = dict(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=32,
        tau=0.10, gamma=0.015, theta=8, window=16,
    )
    base.update(kw)
    return P.EPICConfig(**base)


def _sensor_chunks(seed, n_frames=16, n_obj=4):
    scfg = SYN.StreamConfig(n_frames=n_frames, hw=(FRAME, FRAME), n_obj=n_obj)
    s, _ = SYN.generate_stream(jax.random.PRNGKey(seed), scfg)
    stream = api.SensorChunk(s.frames, s.poses, s.gazes, s.depth)
    return list(api.iter_chunks(stream, CHUNK, remainder="drop"))


def _rand_chunk(rng, t, h, w, dtype, with_depth):
    def arr(shape):
        a = rng.standard_normal(shape)
        if np.issubdtype(np.dtype(dtype), np.integer):
            return (a * 100).astype(dtype)
        return a.astype(dtype)

    return api.SensorChunk(
        arr((t, h, w, 3)),
        arr((t, 4, 4)),
        arr((t, 2)),
        arr((t, h, w)) if with_depth else None,
    )


def _assert_tree_bitwise(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{msg} leaf {i}"
        )


# ---------------------------------------------------------------------------
# Codec: round-trip + rejection


class TestCodec:
    @settings(max_examples=25, deadline=None)
    @given(
        t=st.integers(1, 6),
        h=st.integers(1, 12),
        w=st.integers(1, 12),
        dtype=st.sampled_from(["float32", "float64", "uint8", "int32",
                               "float16", "int64"]),
        with_depth=st.booleans(),
        sid=st.integers(0, 2**63),
        seq=st.integers(0, 2**31),
    )
    def test_roundtrip_property(self, t, h, w, dtype, with_depth, sid, seq):
        rng = np.random.default_rng(t * 1000 + h * 10 + w)
        chunk = _rand_chunk(rng, t, h, w, dtype, with_depth)
        buf = codec.encode_chunk(
            chunk, stream_id=sid, seq=seq, timestamp_ns=17
        )
        frame = codec.decode_frame(buf)
        assert frame.stream_id == sid
        assert frame.seq == seq
        assert frame.timestamp_ns == 17
        assert (frame.chunk.depth is None) == (not with_depth)
        for a, b in zip(chunk, frame.chunk):
            if a is None:
                assert b is None
            else:
                np.testing.assert_array_equal(np.asarray(a), b)
                assert b.dtype == np.dtype(dtype)

    def test_decode_is_zero_copy(self):
        rng = np.random.default_rng(0)
        chunk = _rand_chunk(rng, 4, 8, 8, "float32", True)
        buf = codec.encode_chunk(chunk, stream_id=1, seq=0, timestamp_ns=0)
        frame = codec.decode_frame(buf)
        raw = np.frombuffer(buf, np.uint8)
        for field in frame.chunk:
            assert np.shares_memory(field, raw)

    def test_jax_arrays_encode_and_roundtrip_bitwise(self):
        chunk = _sensor_chunks(0)[0]  # jax arrays
        buf = codec.encode_chunk(chunk, stream_id=5, seq=1, timestamp_ns=2)
        back = codec.decode_frame(buf).chunk
        _assert_tree_bitwise(
            [np.asarray(x) for x in chunk if x is not None],
            [np.asarray(x) for x in back if x is not None],
        )

    def test_frame_nbytes_frames_the_stream(self):
        rng = np.random.default_rng(1)
        chunk = _rand_chunk(rng, 3, 5, 7, "float32", False)
        buf = codec.encode_chunk(chunk, stream_id=1, seq=0, timestamp_ns=0)
        assert codec.frame_nbytes(buf) == len(buf)
        assert codec.frame_nbytes(buf[: codec.FRAME_HEADER.size]) == len(buf)

    def test_rejects_truncated(self):
        rng = np.random.default_rng(2)
        buf = codec.encode_chunk(
            _rand_chunk(rng, 2, 4, 4, "float32", True),
            stream_id=1, seq=0, timestamp_ns=0,
        )
        for cut in (0, 3, codec.FRAME_HEADER.size - 1,
                    codec.DATA_HEADER_NBYTES - 1, len(buf) - 1):
            with pytest.raises(codec.WireFormatError):
                codec.decode_frame(buf[:cut])

    def test_rejects_corrupt_payload_crc(self):
        rng = np.random.default_rng(3)
        buf = bytearray(codec.encode_chunk(
            _rand_chunk(rng, 2, 4, 4, "float32", False),
            stream_id=1, seq=0, timestamp_ns=0,
        ))
        buf[-1] ^= 0x01
        with pytest.raises(codec.WireCRCError):
            codec.decode_frame(bytes(buf))
        # opt-out decodes (trusted transport), bit flip and all
        frame = codec.decode_frame(bytes(buf), verify_crc=False)
        assert frame.chunk.frames.shape == (2, 4, 4, 3)

    def test_rejects_wrong_magic_and_version(self):
        rng = np.random.default_rng(4)
        good = codec.encode_chunk(
            _rand_chunk(rng, 2, 4, 4, "float32", False),
            stream_id=1, seq=0, timestamp_ns=0,
        )
        bad_magic = b"XXXX" + good[4:]
        with pytest.raises(codec.WireFormatError, match="magic"):
            codec.decode_frame(bad_magic)
        bad_version = good[:4] + b"\x63\x00" + good[6:]
        with pytest.raises(codec.WireFormatError, match="version"):
            codec.decode_frame(bad_version)

    def test_rejects_bad_dtype_code_and_size_mismatch(self):
        rng = np.random.default_rng(5)
        good = bytearray(codec.encode_chunk(
            _rand_chunk(rng, 2, 4, 4, "float32", False),
            stream_id=1, seq=0, timestamp_ns=0,
        ))
        bad = bytearray(good)
        bad[codec.FRAME_HEADER.size] = 250  # frames slot dtype code
        with pytest.raises(codec.WireFormatError, match="dtype"):
            codec.decode_frame(bytes(bad))
        # inflate a dim so the field table overruns the payload
        bad = bytearray(good)
        dim_off = codec.FRAME_HEADER.size + 2  # first dim of frames
        bad[dim_off:dim_off + 4] = (1 << 20).to_bytes(4, "little")
        with pytest.raises(codec.WireFormatError):
            codec.decode_frame(bytes(bad))

    def test_decode_validates_cross_field_shapes(self):
        # A frame whose table claims 3 pose rows for 2 video frames
        # must be rejected by SensorChunk validation, not fail deep in
        # the scan later.
        rng = np.random.default_rng(6)
        frames = rng.standard_normal((2, 4, 4, 3)).astype(np.float32)
        poses = rng.standard_normal((3, 4, 4)).astype(np.float32)
        gazes = rng.standard_normal((2, 2)).astype(np.float32)
        payload = (frames.tobytes() + poses.tobytes() + gazes.tobytes())
        header = codec.FRAME_HEADER.pack(
            codec.DATA_MAGIC, codec.WIRE_VERSION, 0, 1, 0, 0,
            zlib.crc32(payload), len(payload),
        )
        table = b"".join(
            codec.FIELD_SLOT.pack(9, arr.ndim, *arr.shape,
                                  *([0] * (6 - arr.ndim)))
            for arr in (frames, poses, gazes)
        ) + codec.FIELD_SLOT.pack(0, 0, 0, 0, 0, 0, 0, 0)
        with pytest.raises(ValueError, match="leading axis"):
            codec.decode_frame(header + table + payload)

    def test_control_and_reply_roundtrip(self):
        ctl = codec.decode_control(codec.encode_control(codec.OP_OPEN, 77))
        assert ctl == codec.ControlFrame(codec.OP_OPEN, 77)
        assert ctl.op_name == "open"
        rep = codec.decode_reply(
            codec.encode_reply(codec.NACK_POOL_FULL, 77, 3)
        )
        assert (rep.status, rep.stream_id, rep.seq) == (
            codec.NACK_POOL_FULL, 77, 3
        )
        assert not rep.ok and rep.status_name == "pool_full"
        kind, frame = codec.decode_message(
            codec.encode_control(codec.OP_CLOSE, 8)
        )
        assert kind == "control" and frame.op == codec.OP_CLOSE
        with pytest.raises(codec.WireFormatError):
            codec.decode_message(b"JUNKJUNKJUNK")


# ---------------------------------------------------------------------------
# Satellites: iter_chunks remainder, SensorChunk validation, ChunkQueue


class TestChunkingSatellites:
    def _stream(self, n=10):
        return api.SensorChunk(
            jnp.arange(n * 4 * 4 * 3, dtype=jnp.float32).reshape(n, 4, 4, 3),
            jnp.tile(jnp.eye(4)[None], (n, 1, 1)),
            jnp.zeros((n, 2)),
            jnp.ones((n, 4, 4)),
        )

    def test_iter_chunks_remainder_modes(self):
        s = self._stream(10)
        assert [c.n_frames for c in api.iter_chunks(s, 4)] == [4, 4, 2]
        assert [
            c.n_frames
            for c in api.iter_chunks(s, 4, remainder="drop")
        ] == [4, 4]
        padded = list(api.iter_chunks(s, 4, remainder="pad"))
        assert [c.n_frames for c in padded] == [4, 4, 4]
        # pad repeats the final frame across every field
        tail = padded[-1]
        for field in tail:
            np.testing.assert_array_equal(
                np.asarray(field[-1]), np.asarray(field[1])
            )
        # the real frames of the padded tail are untouched
        np.testing.assert_array_equal(
            np.asarray(tail.frames[:2]), np.asarray(s.frames[8:10])
        )

    def test_iter_chunks_exact_multiple_identical_across_modes(self):
        s = self._stream(8)
        for mode in ("keep", "drop", "pad"):
            out = list(api.iter_chunks(s, 4, remainder=mode))
            assert [c.n_frames for c in out] == [4, 4]

    def test_iter_chunks_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="remainder"):
            list(api.iter_chunks(self._stream(8), 4, remainder="wrap"))

    def test_sensor_chunk_validation(self):
        s = self._stream(8)
        assert s.validate() is s
        bad_t = api.SensorChunk(s.frames, s.poses[:5], s.gazes, s.depth)
        with pytest.raises(ValueError, match="leading axis"):
            bad_t.validate()
        with pytest.raises(ValueError, match="leading axis"):
            bad_t.slice(0, 4)
        bad_hw = api.SensorChunk(
            s.frames, s.poses, s.gazes, s.depth[:, :2, :]
        )
        with pytest.raises(ValueError, match="depth"):
            bad_hw.validate()

    def test_chunk_queue_timestamps_and_policies(self):
        clock_now = [0.0]
        q = ChunkQueue(2, clock=lambda: clock_now[0])
        q.push("a")
        clock_now[0] = 1.5
        q.push("b")
        assert not q.push("c")  # refuse-newest default
        assert q.n_overflow == 1 and q.n_dropped == 0
        chunk, ts = q.pop_entry()
        assert (chunk, ts) == ("a", 0.0)
        assert q.pop() == "b"  # legacy signature intact

        q2 = ChunkQueue(2, policy="drop_oldest", clock=lambda: 0.0)
        assert q2.push("a") and q2.push("b") and q2.push("c")
        assert q2.n_dropped == 1 and q2.n_overflow == 0
        assert [q2.pop(), q2.pop()] == ["b", "c"]
        with pytest.raises(ValueError, match="policy"):
            ChunkQueue(2, policy="refuse_oldest")
        with pytest.raises(ValueError, match="policy"):
            StreamServer(
                api.EPICCompressor(_ecfg()),
                ServerConfig(queue_policy="nope"),
            )


# ---------------------------------------------------------------------------
# Latency histogram math


class TestLatency:
    def test_percentiles_bracket_samples(self):
        h = LatencyHistogram()
        for ms in range(1, 101):  # 1..100 ms uniform
            h.record(ms * 1e-3)
        s = h.summary()
        assert s["count"] == 100
        assert 40 <= s["p50_ms"] <= 62
        assert 85 <= s["p95_ms"] <= 100
        assert 94 <= s["p99_ms"] <= 100
        assert s["max_ms"] == 100.0
        assert h.percentile(1.0) <= 100.0 * 1e-3 + 1e-9

    def test_empty_and_extremes(self):
        h = LatencyHistogram()
        # empty percentiles are nan (defined, propagating); summaries
        # render them as None to stay JSON-safe
        assert math.isnan(h.percentile(0.5))
        assert h.summary()["p99_ms"] is None
        h.record(0.0)  # below the 1 µs floor -> underflow bucket
        h.record(1e9)  # absurd -> overflow bucket, max preserved
        assert h.n == 2
        assert h.max_s == 1e9

    def test_merge_matches_combined(self):
        a, b, c = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
        rng = np.random.default_rng(0)
        for _ in range(200):
            x = float(rng.lognormal(-4, 1))
            a.record(x) if rng.random() < 0.5 else b.record(x)
            c.record(x)
        a.merge(b)
        assert a.n == c.n
        assert a.counts == c.counts
        assert math.isclose(a.percentile(0.99), c.percentile(0.99))

    def test_recorder_splits_queue_and_service(self):
        r = LatencyRecorder()
        r.observe(0.0, 0.3, 1.0)
        r.observe(0.0, 0.1, 0.2)
        s = r.summary()
        assert s["total"]["count"] == 2
        assert s["queue_wait"]["max_ms"] == pytest.approx(300.0, rel=0.1)
        assert s["service"]["max_ms"] == pytest.approx(700.0, rel=0.1)


# ---------------------------------------------------------------------------
# Ingest server: loopback parity with in-process sessions


class TestLoopbackIngest:
    def _wire_server(self, capacity=2, k_ladder=None, **kw):
        srv = StreamServer(
            api.EPICCompressor(_ecfg(prefilter_k=8 if k_ladder else 0)),
            ServerConfig(
                capacity=capacity, chunk_frames=CHUNK, queue_depth=2,
                k_ladder=k_ladder, **kw,
            ),
        )
        ingest = IngestServer(srv)
        return srv, ingest, Loopback(ingest)

    def test_open_submit_close_protocol(self):
        srv, ingest, loop = self._wire_server()
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        assert not loop.send(
            codec.encode_control(codec.OP_OPEN, 1)
        ).ok  # duplicate
        chunk = _sensor_chunks(0)[0]
        msg = codec.encode_chunk(chunk, stream_id=1, seq=0, timestamp_ns=0)
        assert loop.send(msg).ok
        unknown = codec.encode_chunk(
            chunk, stream_id=9, seq=0, timestamp_ns=0
        )
        assert loop.send(unknown).status_name == "unknown_stream"
        assert loop.send(b"garbage").status_name == "bad_frame"
        # close drains the queued chunk, then evicts
        assert loop.send(codec.encode_control(codec.OP_CLOSE, 1)).ok
        assert srv.live_sessions == []
        assert srv.frames_served == CHUNK
        c = ingest.counters()
        assert (c["n_opened"], c["n_closed"], c["n_frames_in"]) == (1, 1, 1)

    def test_backpressure_and_pool_full_nacks(self):
        srv, ingest, loop = self._wire_server(capacity=1)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        assert loop.send(
            codec.encode_control(codec.OP_OPEN, 2)
        ).status_name == "pool_full"
        chunk = _sensor_chunks(0)[0]
        for seq in range(2):
            assert loop.send(codec.encode_chunk(
                chunk, stream_id=1, seq=seq, timestamp_ns=0
            )).ok
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=2, timestamp_ns=0
        ))
        assert r.status_name == "backpressure" and r.seq == 2
        assert ingest.nacks == {"pool_full": 1, "backpressure": 1}
        assert srv.n_backpressure == 1

    def test_out_of_order_and_duplicate_seq_nacked(self):
        srv, ingest, loop = self._wire_server()
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        chunk = _sensor_chunks(0)[0]

        def send(seq):
            return loop.send(codec.encode_chunk(
                chunk, stream_id=1, seq=seq, timestamp_ns=0
            ))

        assert send(0).ok
        # a duplicate of an accepted seq is refused, not double-served
        r = send(0)
        assert r.status_name == "out_of_order" and r.seq == 0
        srv.tick()
        # a regressed seq after progress is refused too
        assert send(5).ok
        srv.tick()
        assert send(3).status_name == "out_of_order"
        # gaps forward are fine (producers may drop frames)
        assert send(9).ok
        c = ingest.counters()
        assert c["n_out_of_order"] == 2
        assert c["nacks"]["out_of_order"] == 2
        assert c["n_frames_in"] == 3
        assert srv.frames_served == 2 * CHUNK  # dup/regressed never served

    def test_backpressure_retry_of_same_seq_still_acks(self):
        """`_seq_seen` only advances on successful submit: a producer
        retrying the seq that was NACKed with backpressure must ACK
        once the queue drains (the loadgen relies on this)."""
        srv, ingest, loop = self._wire_server(capacity=1)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        chunk = _sensor_chunks(0)[0]
        for seq in range(2):
            assert loop.send(codec.encode_chunk(
                chunk, stream_id=1, seq=seq, timestamp_ns=0
            )).ok
        retry = codec.encode_chunk(chunk, stream_id=1, seq=2, timestamp_ns=0)
        assert loop.send(retry).status_name == "backpressure"
        srv.tick()  # drains one queued chunk
        assert loop.send(retry).ok
        assert ingest.counters()["n_out_of_order"] == 0

    def test_loopback_parity_fixed_k(self):
        chunks = {sid: _sensor_chunks(sid, n_frames=16) for sid in (1, 2)}
        srv, ingest, loop = self._wire_server(capacity=2)
        for sid in chunks:
            assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
        for seq in range(2):
            for sid in chunks:
                assert loop.send(codec.encode_chunk(
                    chunks[sid][seq], stream_id=sid, seq=seq,
                    timestamp_ns=seq,
                )).ok
            ingest.tick()
        for sid in chunks:
            comp = api.EPICCompressor(_ecfg())
            step = jax.jit(comp.step)
            state = comp.init()
            for c in chunks[sid]:
                state, _ = step(state, c)
            _assert_tree_bitwise(
                state, srv.state(sid), f"stream {sid}"
            )

    def test_loopback_parity_adaptive_k_trajectory(self):
        ladder = (8, 16, 32)
        chunks = _sensor_chunks(3, n_frames=24, n_obj=5)
        srv, ingest, loop = self._wire_server(capacity=2, k_ladder=ladder)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 7)).ok
        for seq, c in enumerate(chunks):
            assert loop.send(codec.encode_chunk(
                c, stream_id=7, seq=seq, timestamp_ns=seq
            )).ok
            ingest.tick()
        solo = api.EPICCompressor(
            _ecfg(prefilter_k=8), k_ladder=ladder
        )
        state = solo.init()
        for c in chunks:
            state, _ = solo.step(state, c)
        _assert_tree_bitwise(state, srv.state(7), "adaptive state")
        assert solo.k_trajectory == srv.telemetry(7).k_trajectory

    def test_tick_prunes_server_side_evictions(self):
        srv, ingest, loop = self._wire_server(
            capacity=2, eviction="idle", idle_frames=CHUNK
        )
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        ingest.tick()  # idle >= CHUNK frames -> evicted by policy
        assert srv.live_sessions == []
        chunk = _sensor_chunks(0)[0]
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=0, timestamp_ns=0
        ))
        assert r.status_name == "unknown_stream"

    def test_latency_recorder_attaches(self):
        srv, ingest, loop = self._wire_server()
        srv.latency = LatencyRecorder()
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        chunk = _sensor_chunks(0)[0]
        for seq in range(2):
            loop.send(codec.encode_chunk(
                chunk, stream_id=1, seq=seq, timestamp_ns=0
            ))
            ingest.tick()
        s = srv.latency.summary()
        assert s["total"]["count"] == 2
        assert s["total"]["p99_ms"] > 0
        # total = queue_wait + service, histogram-bucket tolerance
        assert s["total"]["max_ms"] >= s["service"]["max_ms"]


# ---------------------------------------------------------------------------
# Trace record/playback


class TestTrace:
    def test_record_replay_bitwise_state_parity(self, tmp_path):
        chunks = _sensor_chunks(5, n_frames=16)
        path = os.path.join(tmp_path, "session.wtrace")
        n = trace.record_session(
            chunks, path, stream_id=11, chunk_period_ns=1000,
            open_close=False,
        )
        assert n == len(chunks)

        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=2),
        )
        ingest = IngestServer(srv)
        loop = Loopback(ingest)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 11)).ok
        replies = []
        trace.replay(path, loop.send, on_reply=replies.append)
        assert all(r.ok for r in replies)
        while srv.live_sessions and any(
            len(srv._queues[s]) for s in srv.live_sessions
        ):
            ingest.tick()

        comp = api.EPICCompressor(_ecfg())
        step = jax.jit(comp.step)
        state = comp.init()
        for c in chunks:
            state, _ = step(state, c)
        _assert_tree_bitwise(state, srv.state(11), "trace replay")

    def test_trace_roundtrips_messages_bitwise(self, tmp_path):
        chunks = _sensor_chunks(6, n_frames=16)
        msgs = [codec.encode_control(codec.OP_OPEN, 3)] + [
            codec.encode_chunk(c, stream_id=3, seq=i, timestamp_ns=i * 10)
            for i, c in enumerate(chunks)
        ]
        path = os.path.join(tmp_path, "t.wtrace")
        with trace.TraceWriter(path) as w:
            for i, m in enumerate(msgs):
                w.append(m, timestamp_ns=i * 1000)
        recs = trace.TraceReader(path).records()
        assert [r.timestamp_ns for r in recs] == [
            i * 1000 for i in range(len(msgs))
        ]
        for rec, msg in zip(recs, msgs):
            assert bytes(rec.message) == msg
        # decoded payloads are views of the reader's buffer (no copy)
        frame = codec.decode_frame(recs[1].message)
        assert frame.chunk.frames.base is not None

    def test_realtime_replay_paces_by_timestamps(self, tmp_path):
        path = os.path.join(tmp_path, "p.wtrace")
        with trace.TraceWriter(path) as w:
            for i in range(3):
                w.append(
                    codec.encode_control(codec.OP_OPEN, i),
                    timestamp_ns=i * 1_000_000_000,
                )
        sleeps = []
        sent = []
        trace.replay(
            path, lambda m: sent.append(bytes(m)),
            realtime=True, speed=10.0, sleep=sleeps.append,
        )
        assert len(sent) == 3
        # 1 s gaps at 10x; the injected sleep doesn't advance the wall
        # clock, so the lags accumulate: ~0.1 s then ~0.2 s.
        assert len(sleeps) == 2
        assert sleeps[0] == pytest.approx(0.1, abs=0.02)
        assert sleeps[1] == pytest.approx(0.2, abs=0.02)

    def test_reader_rejects_garbage_and_truncation(self, tmp_path):
        bad = os.path.join(tmp_path, "bad.wtrace")
        with open(bad, "wb") as f:
            f.write(b"NOTATRACE123")
        with pytest.raises(codec.WireFormatError):
            trace.TraceReader(bad)
        trunc = os.path.join(tmp_path, "trunc.wtrace")
        with trace.TraceWriter(trunc) as w:
            w.append(codec.encode_control(codec.OP_OPEN, 1))
        with open(trunc, "rb") as f:
            data = f.read()
        with open(trunc, "wb") as f:
            f.write(data[:-3])
        with pytest.raises(codec.WireFormatError, match="truncated"):
            trace.TraceReader(trunc).records()


# ---------------------------------------------------------------------------
# Load generator determinism


class TestLoadGen:
    def _run(self, seed=3):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=1),
        )
        srv.latency = LatencyRecorder()
        ingest = IngestServer(srv)
        cfg = LoadConfig(
            seed=seed, ticks=8, arrival_rate=1.0,
            session_len_mu=1.0, session_len_sigma=0.5,
            burst_factor=2.0, burst_every=4, submit_per_tick=1,
        )
        bank = _sensor_chunks(0, n_frames=16)
        summary = LoadGen(cfg, bank, ingest).run()
        return summary, srv

    def test_seeded_run_is_deterministic(self):
        s1, srv1 = self._run()
        s2, srv2 = self._run()
        # client-side RTT percentiles are wall-clock (their *count* is
        # deterministic, the timings are not): compare them apart from
        # the seeded-deterministic remainder
        rtt1, rtt2 = s1.pop("rtt"), s2.pop("rtt")
        assert rtt1["count"] == rtt2["count"] > 0
        assert s1 == s2
        # the latency sample count is part of the deterministic shape
        assert (
            srv1.latency.summary()["total"]["count"]
            == srv2.latency.summary()["total"]["count"]
        )
        assert s1["n_frames_acked"] > 0
        assert s1["n_sessions"] > 0

    def test_different_seed_changes_schedule(self):
        s1, _ = self._run(seed=3)
        s2, _ = self._run(seed=4)
        assert s1["event_log_sha"] != s2["event_log_sha"]

    def test_burst_exercises_backpressure(self):
        # queue_depth=1 + 2x burst sends must produce backpressure NACKs
        s, _ = self._run()
        assert s["nacks"].get("backpressure", 0) > 0

    def test_validation(self):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK),
        )
        ingest = IngestServer(srv)
        with pytest.raises(ValueError, match="bank"):
            LoadGen(LoadConfig(), [], ingest)
        with pytest.raises(ValueError, match="burst_factor"):
            LoadGen(
                LoadConfig(burst_factor=0.5),
                _sensor_chunks(0), ingest,
            )


# ---------------------------------------------------------------------------
# Socket transport (TCP loopback interface)


class TestSocketTransport:
    def test_tcp_roundtrip_and_state_parity(self):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=2),
        )
        ingest = IngestServer(srv)
        try:
            host, port = ingest.start_tcp_in_thread()
        except (OSError, PermissionError) as e:  # pragma: no cover
            pytest.skip(f"cannot bind local TCP socket: {e}")
        try:
            chunks = _sensor_chunks(8, n_frames=16)
            with WireClient(host, port) as client:
                assert client.send(
                    codec.encode_control(codec.OP_OPEN, 21)
                ).ok
                for seq, c in enumerate(chunks):
                    r = client.send(codec.encode_chunk(
                        c, stream_id=21, seq=seq, timestamp_ns=seq
                    ))
                    assert r.ok and r.seq == seq
                    ingest.tick()
            comp = api.EPICCompressor(_ecfg())
            step = jax.jit(comp.step)
            state = comp.init()
            for c in chunks:
                state, _ = step(state, c)
            _assert_tree_bitwise(state, srv.state(21), "tcp ingest")
            assert ingest.counters()["n_frames_in"] == len(chunks)
        finally:
            ingest.stop()


# ---------------------------------------------------------------------------
# Reconnect/resume: RESUME handshake, seq gaps, windowed replay


from repro.wire.server import ResumableSession, ResumeError  # noqa: E402


class _Drop(ConnectionError):
    pass


class _DroppingTransport:
    """Loopback that drops the connection on scheduled sends:
    ``after`` seqs are delivered first (the ACK is lost — exercises
    duplicate suppression); ``before`` seqs are lost entirely (the
    frame must be replayed)."""

    def __init__(self, loop, *, before=(), after=()):
        self.loop = loop
        self.before = set(before)
        self.after = set(after)

    def _seq(self, msg):
        kind, frame = codec.decode_message(msg)
        return frame.seq if kind == "data" else None

    def send(self, msg):
        seq = self._seq(msg)
        if seq in self.before:
            self.before.discard(seq)
            raise _Drop(f"dropped before delivering seq {seq}")
        reply = self.loop.send(msg)
        if seq in self.after:
            self.after.discard(seq)
            raise _Drop(f"dropped after delivering seq {seq}")
        return reply


class _StubTransport:
    def __init__(self, replies):
        self.replies = list(replies)
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)
        return self.replies.pop(0)


class TestResume:
    def _wire_server(self, **kw):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=2),
        )
        ingest = IngestServer(srv, **kw)
        return srv, ingest, Loopback(ingest)

    def test_resume_codec_roundtrip(self):
        msg = codec.encode_resume(9, 41)
        ctl = codec.decode_control(msg)
        assert ctl.op == codec.OP_RESUME
        assert ctl.op_name == "resume"
        assert (ctl.stream_id, ctl.seq) == (9, 42)  # wire carries +1
        fresh = codec.decode_control(codec.encode_resume(9, -1))
        assert fresh.seq == 0
        with pytest.raises(codec.WireFormatError, match="encode_resume"):
            codec.encode_control(codec.OP_RESUME, 9)
        with pytest.raises(codec.WireFormatError, match=">= -1"):
            codec.encode_resume(9, -2)
        with pytest.raises(codec.WireFormatError, match="truncated"):
            codec.decode_control(msg[: codec.CONTROL.size])
        kind, ctl2 = codec.decode_message(msg)
        assert kind == "control" and ctl2 == ctl

    def test_resume_handshake_and_dup_suppression(self):
        srv, ingest, loop = self._wire_server()
        chunk = _sensor_chunks(0)[0]
        assert loop.send(codec.encode_control(codec.OP_OPEN, 5)).ok
        for seq in range(3):
            assert loop.send(codec.encode_chunk(
                chunk, stream_id=5, seq=seq, timestamp_ns=0,
            )).ok
            ingest.tick()
        served = ingest.counters()["n_frames_in"]
        # client lost ACKs for 1 and 2: RESUME says resume from seq 2
        r = loop.send(codec.encode_resume(5, 0))
        assert r.ok and r.seq == 3  # server already has through seq 2
        for seq in (1, 2):  # window replay overlaps the server cursor
            r = loop.send(codec.encode_chunk(
                chunk, stream_id=5, seq=seq, timestamp_ns=0,
            ))
            assert r.ok  # suppressed, not out_of_order
        c = ingest.counters()
        assert c["n_resumed"] == 1
        assert c["n_dup_suppressed"] == 2
        assert c["n_frames_in"] == served  # nothing double-served
        # beyond the resume cursor a regressed seq is still refused
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=5, seq=4, timestamp_ns=0,
        ))
        assert r.ok
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=5, seq=3, timestamp_ns=0,
        ))
        assert r.status_name == "out_of_order"

    def test_resume_unknown_stream_nacked(self):
        _, _, loop = self._wire_server()
        r = loop.send(codec.encode_resume(404, 7))
        assert r.status_name == "unknown_stream"

    def test_resume_adopts_cursor_for_restored_slot(self):
        """A slot live in the StreamServer but unknown to this ingest
        frontier (restored from a checkpoint without wire metadata)
        adopts the client's claimed cursor."""
        srv, ingest, loop = self._wire_server()
        srv.admit(8)  # admitted out-of-band, no wire OPEN
        chunk = _sensor_chunks(1)[0]
        r = loop.send(codec.encode_resume(8, 4))
        assert r.ok and r.seq == 5
        assert ingest._seq_seen[8] == 4
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=8, seq=5, timestamp_ns=0,
        ))
        assert r.ok
        assert ingest.counters()["n_seq_gaps"] == 0

    def test_seq_gaps_counted_in_lax_mode(self):
        srv, ingest, loop = self._wire_server()
        chunk = _sensor_chunks(0)[0]
        assert loop.send(codec.encode_control(codec.OP_OPEN, 3)).ok
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=3, seq=2, timestamp_ns=0,  # 0,1 lost
        )).ok
        ingest.tick()
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=3, seq=6, timestamp_ns=0,  # 3,4,5 lost
        )).ok
        c = ingest.counters()
        assert c["n_seq_gaps"] == 5
        assert c["seq_gaps_by_stream"] == {3: 5}
        assert c["nacks"] == {}  # lax: counted, never refused

    def test_strict_seq_nacks_gaps(self):
        srv, ingest, loop = self._wire_server(strict_seq=True)
        chunk = _sensor_chunks(0)[0]
        assert loop.send(codec.encode_control(codec.OP_OPEN, 3)).ok
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=3, seq=0, timestamp_ns=0,
        )).ok
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=3, seq=2, timestamp_ns=0,
        ))
        assert r.status_name == "seq_gap"
        assert ingest.counters()["n_frames_in"] == 1  # gap not served
        # the retransmit closes the gap; the original jump then lands
        for seq in (1, 2):
            ingest.tick()
            assert loop.send(codec.encode_chunk(
                chunk, stream_id=3, seq=seq, timestamp_ns=0,
            )).ok
        c = ingest.counters()
        assert c["n_seq_gaps"] == 1
        assert c["nacks"]["seq_gap"] == 1
        assert c["n_frames_in"] == 3

    def test_resumable_session_recovers_both_drop_kinds(self):
        """Drops before delivery (frame lost) and after delivery (ACK
        lost) both self-heal through reconnect+RESUME+replay, and the
        served state stays bitwise identical to a clean session."""
        chunks = _sensor_chunks(4, n_frames=32)
        srv, ingest, loop = self._wire_server()
        sess = ResumableSession(
            _DroppingTransport(loop, before={1}, after={2}),
            6,
            drain=ingest.tick,
        )
        assert sess.open().ok
        for c in chunks:
            assert sess.send_chunk(c).ok
            ingest.tick()
        while any(len(q) for q in srv._queues.values()):
            ingest.tick()
        assert sess.n_resumes == 2
        assert ingest.counters()["n_resumed"] == 2
        assert ingest.counters()["n_dup_suppressed"] >= 1  # ACK-lost seq

        comp = api.EPICCompressor(_ecfg())
        step = jax.jit(comp.step)
        state = comp.init()
        for c in chunks:
            state, _ = step(state, c)
        _assert_tree_bitwise(state, srv.state(6), "resumed session")

    def test_resume_refused_raises(self):
        stub = _StubTransport(
            [codec.Reply(codec.NACK_UNKNOWN_STREAM, 1, 0)]
        )
        sess = ResumableSession(stub, 1)
        with pytest.raises(ResumeError, match="unknown_stream"):
            sess.resume()

    def test_resume_gap_outlives_window(self):
        """Server wants a seq the bounded window already rolled past."""
        stub = _StubTransport([codec.Reply(codec.ACK, 1, 1)])
        sess = ResumableSession(stub, 1, window=2)
        sess.next_seq = 5
        sess.last_acked = 0
        sess._window.append((3, b"m3"))
        sess._window.append((4, b"m4"))
        with pytest.raises(ResumeError, match="window"):
            sess.resume()

    def test_resume_noop_when_server_caught_up(self):
        stub = _StubTransport([codec.Reply(codec.ACK, 1, 4)])
        sess = ResumableSession(stub, 1, window=4)
        sess.next_seq = 4
        sess.last_acked = 1  # ACKs lost but the server has everything
        assert sess.resume() == 0
        assert sess.n_resumes == 1


class TestWireClientReconnect:
    class _FakeSock:
        def close(self):
            pass

    def _client(self, monkeypatch, fail_times, **kw):
        attempts = []
        sleeps = []
        fake = self._FakeSock()

        def create(addr, timeout=None):
            attempts.append(addr)
            if 0 < len(attempts) <= fail_times + 1 and len(attempts) > 1:
                if len(attempts) - 1 <= fail_times:
                    raise OSError("connection refused")
            return fake

        monkeypatch.setattr(
            "repro.wire.server.socket.create_connection", create
        )
        cli = WireClient(
            "127.0.0.1", 1, sleep=sleeps.append, **kw
        )
        return cli, attempts, sleeps

    def test_backoff_schedule_bounded_and_exponential(self, monkeypatch):
        cli, attempts, sleeps = self._client(
            monkeypatch, fail_times=3,
            reconnect_attempts=5, backoff_base=0.05, backoff_max=0.15,
        )
        cli.reconnect()
        # 1 construction dial + 3 refused + 1 success
        assert len(attempts) == 5
        assert cli.n_reconnects == 1
        assert sleeps == [0.05, 0.1, 0.15]  # doubled, then capped

    def test_reconnect_gives_up_after_bounded_attempts(self, monkeypatch):
        cli, attempts, sleeps = self._client(
            monkeypatch, fail_times=99,
            reconnect_attempts=3, backoff_base=0.01,
        )
        with pytest.raises(ConnectionError, match="after 3 attempts"):
            cli.reconnect()
        assert len(attempts) == 4  # construction + 3 redials
        assert len(sleeps) == 3
        assert cli.n_reconnects == 0


# ---------------------------------------------------------------------------
# Credit-based flow control + selective retransmit (strict-seq loop)


class TestCreditFlow:
    def _wire_server(self, **kw):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(
                capacity=2, chunk_frames=CHUNK,
                queue_depth=kw.pop("queue_depth", 2),
            ),
        )
        ingest = IngestServer(srv, **kw)
        return srv, ingest, Loopback(ingest)

    def test_credit_codec_roundtrip(self):
        msg = codec.encode_credit(7, 12)
        ctl = codec.decode_control(msg)
        assert ctl.op == codec.OP_CREDIT
        assert ctl.op_name == "credit"
        assert (ctl.stream_id, ctl.seq) == (7, 12)
        kind, ctl2 = codec.decode_message(msg)
        assert kind == "control" and ctl2 == ctl
        with pytest.raises(codec.WireFormatError, match="encode_credit"):
            codec.encode_control(codec.OP_CREDIT, 7)
        with pytest.raises(codec.WireFormatError, match=">= 1"):
            codec.encode_credit(7, 0)
        with pytest.raises(codec.WireFormatError, match="truncated"):
            codec.decode_control(msg[: codec.CONTROL.size])

    @settings(max_examples=40, deadline=None)
    @given(data=st.data())
    def test_all_control_frames_roundtrip(self, data):
        """Property: every control op (OPEN/CLOSE/RESUME/CREDIT)
        round-trips its stream id and payload bit-exactly."""
        op = data.draw(st.sampled_from(
            (codec.OP_OPEN, codec.OP_CLOSE, codec.OP_RESUME,
             codec.OP_CREDIT)
        ))
        sid = data.draw(st.integers(0, 2**64 - 1))
        if op == codec.OP_RESUME:
            last_acked = data.draw(st.integers(-1, 2**32))
            msg = codec.encode_resume(sid, last_acked)
            expect_seq = last_acked + 1
        elif op == codec.OP_CREDIT:
            requested = data.draw(st.integers(1, 2**32))
            msg = codec.encode_credit(sid, requested)
            expect_seq = requested
        else:
            msg = codec.encode_control(op, sid)
            expect_seq = 0
        ctl = codec.decode_control(msg)
        assert (ctl.op, ctl.stream_id, ctl.seq) == (op, sid, expect_seq)
        assert ctl.op_name == codec._OPS[op]
        kind, ctl2 = codec.decode_message(msg)
        assert kind == "control" and ctl2 == ctl

    def test_every_nack_status_has_exactly_one_reason(self):
        """Table-driven: STATUS_REASONS covers exactly the codes in
        STATUS_NAMES, one non-empty, distinct string each."""
        assert set(codec.STATUS_REASONS) == set(codec.STATUS_NAMES)
        rows = sorted(
            (status, codec.STATUS_NAMES[status],
             codec.STATUS_REASONS[status])
            for status in codec.STATUS_NAMES
        )
        for status, name, reason in rows:
            assert isinstance(reason, str) and reason.strip(), name
        assert len({reason for *_, reason in rows}) == len(rows)

    def test_grant_sized_to_queue_headroom(self):
        srv, ingest, loop = self._wire_server(queue_depth=2)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        # empty queue: grant = min(requested, headroom)
        r = loop.send(codec.encode_credit(1, 10))
        assert r.ok and r.seq == 2
        # the grant is outstanding: no headroom left to re-grant
        assert loop.send(codec.encode_credit(1, 10)).seq == 0
        chunk = _sensor_chunks(0)[0]
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=0, timestamp_ns=0
        )).ok  # consumes one credit; queue now holds one chunk
        assert loop.send(codec.encode_credit(1, 10)).seq == 0
        ingest.tick()  # queue drains: headroom 2, outstanding 1
        r = loop.send(codec.encode_credit(1, 10))
        assert r.ok and r.seq == 1
        c = ingest.counters()
        assert c["n_credit_requests"] == 4
        assert c["n_credit_granted"] == 3
        assert c["credit_outstanding"] == 2
        # unknown stream: refused with the usual NACK
        r = loop.send(codec.encode_credit(404, 1))
        assert r.status_name == "unknown_stream"

    def test_resume_and_close_void_grants(self):
        srv, ingest, loop = self._wire_server(queue_depth=2)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        assert loop.send(codec.encode_credit(1, 2)).seq == 2
        assert loop.send(codec.encode_resume(1, -1)).ok
        assert ingest.counters()["credit_outstanding"] == 0
        # a fresh request re-grants from scratch
        assert loop.send(codec.encode_credit(1, 2)).seq == 2
        assert loop.send(codec.encode_control(codec.OP_CLOSE, 1)).ok
        assert ingest.counters()["credit_outstanding"] == 0

    def test_session_paces_on_credit_no_backpressure(self):
        chunks = _sensor_chunks(7, n_frames=48)
        # without credit: blind sends into a depth-1 queue NACK
        srv_a, ingest_a, loop_a = self._wire_server(queue_depth=1)
        sess_a = ResumableSession(loop_a, 3, drain=ingest_a.tick)
        assert sess_a.open().ok
        for c in chunks:
            assert sess_a.send_chunk(c).ok
        assert srv_a.n_backpressure > 0
        # with credit: the session asks first and never hits the wall
        srv_b, ingest_b, loop_b = self._wire_server(queue_depth=1)
        sess_b = ResumableSession(
            loop_b, 3, drain=ingest_b.tick, credit=4
        )
        assert sess_b.open().ok
        for c in chunks:
            assert sess_b.send_chunk(c).ok
        assert srv_b.n_backpressure == 0
        assert sess_b.n_credit_requests > 0
        assert sess_b.n_credit_waits > 0  # zero grants paced via drain
        while any(len(q) for q in srv_b._queues.values()):
            ingest_b.tick()
        while any(len(q) for q in srv_a._queues.values()):
            ingest_a.tick()
        _assert_tree_bitwise(
            srv_a.state(3), srv_b.state(3), "credit pacing"
        )

    def test_credit_starvation_without_drain_raises(self):
        srv, ingest, loop = self._wire_server(queue_depth=1)
        sess = ResumableSession(loop, 2, credit=1, max_retries=3)
        assert sess.open().ok
        chunk = _sensor_chunks(0)[0]
        assert sess.send_chunk(chunk).ok  # grant 1, consume 1
        with pytest.raises(ResumeError, match="no drain hook"):
            sess.send_chunk(chunk)  # queue full -> zero grant, no drain

    def test_credit_validation(self):
        with pytest.raises(ValueError, match="credit window"):
            ResumableSession(object(), 1, credit=0)


class _SwallowingTransport:
    """Silently loses data frames with scheduled seqs (synthesizing the
    ACK a fire-and-forget uplink would assume), delivering the rest."""

    def __init__(self, loop, lose=()):
        self.loop = loop
        self.lose = set(lose)

    def send(self, msg):
        if bytes(memoryview(msg)[:4]) == codec.DATA_MAGIC:
            _, _, _, sid, seq, *_ = codec.FRAME_HEADER.unpack_from(
                bytes(msg)[: codec.FRAME_HEADER.size]
            )
            if seq in self.lose:
                self.lose.discard(seq)
                return codec.Reply(codec.ACK, sid, seq)
        return self.loop.send(msg)


class TestSelectiveRetransmit:
    def _strict(self, **kw):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=4),
        )
        ingest = IngestServer(srv, strict_seq=True, **kw)
        return srv, ingest, Loopback(ingest)

    def test_gap_nack_carries_first_missing_seq(self):
        srv, ingest, loop = self._strict()
        chunk = _sensor_chunks(0)[0]
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        # nothing served yet: the first missing seq is 0
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=2, timestamp_ns=0
        ))
        assert r.status_name == "seq_gap" and r.seq == 0
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=0, timestamp_ns=0
        )).ok
        # served through 0: a jump to 3 is missing [1, 3)
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=3, timestamp_ns=0
        ))
        assert r.status_name == "seq_gap" and r.seq == 1

    def test_session_replays_exactly_the_missing_slice(self):
        chunks = _sensor_chunks(9, n_frames=48)
        srv, ingest, loop = self._strict()
        sess = ResumableSession(
            _SwallowingTransport(loop, lose={1, 2}),
            5, window=32, drain=ingest.tick,
        )
        assert sess.open().ok
        for c in chunks:
            assert sess.send_chunk(c).ok
            ingest.tick()
        while any(len(q) for q in srv._queues.values()):
            ingest.tick()
        # seqs 1 and 2 were lost in flight; seq 3's NACK named the
        # range and exactly those two frames were replayed
        assert sess.n_retransmits == 2
        assert ingest.counters()["n_frames_in"] == len(chunks)
        comp = api.EPICCompressor(_ecfg())
        step = jax.jit(comp.step)
        state = comp.init()
        for c in chunks:
            state, _ = step(state, c)
        _assert_tree_bitwise(state, srv.state(5), "selective retransmit")

    def test_loss_outliving_window_is_an_error(self):
        chunks = _sensor_chunks(9, n_frames=40)
        srv, ingest, loop = self._strict()
        sess = ResumableSession(
            _SwallowingTransport(loop, lose={0, 1}),
            6, window=2, drain=ingest.tick,
        )
        assert sess.open().ok
        assert sess.send_chunk(chunks[0]).ok  # lost, ACK synthesized
        assert sess.send_chunk(chunks[1]).ok  # lost, ACK synthesized
        # seq 2 pushes seq 0 out of the 2-frame window; the server's
        # gap starts at 0, which the window can no longer supply
        with pytest.raises(ResumeError, match="outlived"):
            sess.send_chunk(chunks[2])


# ---------------------------------------------------------------------------
# Multi-stream traces: interleaving recorded and replayed bit-exactly


class TestMultiStreamTrace:
    def test_record_streams_message_order(self, tmp_path):
        feeds = {
            1: _sensor_chunks(1, n_frames=24),  # 3 chunks
            2: _sensor_chunks(2, n_frames=16),  # 2 chunks
        }
        path = os.path.join(tmp_path, "multi.wtrace")
        n = trace.record_streams(feeds, path, chunk_period_ns=1000)
        assert n == 2 + 5 + 2  # OPENs + data + CLOSEs
        decoded = []
        for rec in trace.TraceReader(path):
            kind, frame = codec.decode_message(rec.message)
            decoded.append((
                rec.timestamp_ns,
                frame.op_name if kind == "control" else "data",
                frame.stream_id,
                frame.seq if kind == "data" else None,
            ))
        assert decoded == [
            (0, "open", 1, None), (0, "data", 1, 0),
            (0, "open", 2, None), (0, "data", 2, 0),
            (1000, "data", 1, 1), (1000, "data", 2, 1),
            (2000, "data", 1, 2), (2000, "close", 2, None),
            (3000, "close", 1, None),
        ]

    def test_interleaved_replay_reaches_bitwise_state_parity(
        self, tmp_path
    ):
        feeds = {
            1: _sensor_chunks(1, n_frames=32),
            2: _sensor_chunks(2, n_frames=24),
        }
        path = os.path.join(tmp_path, "multi.wtrace")
        trace.record_streams(
            feeds, path, chunk_period_ns=1000, open_close=False
        )
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=2),
        )
        ingest = IngestServer(srv)
        loop = Loopback(ingest)
        for sid in feeds:
            assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
        ticks = []
        replies = []
        trace.replay(
            path, loop.send,
            on_reply=replies.append,
            on_advance=lambda: ticks.append(ingest.tick()),
        )
        assert all(r.ok for r in replies)
        assert len(ticks) == 3  # 4 distinct timestamps -> 3 boundaries
        while any(len(q) for q in srv._queues.values()):
            ingest.tick()
        for sid, chunks in feeds.items():
            comp = api.EPICCompressor(_ecfg())
            step = jax.jit(comp.step)
            state = comp.init()
            for c in chunks:
                state, _ = step(state, c)
            _assert_tree_bitwise(
                state, srv.state(sid), f"interleaved stream {sid}"
            )

    def _loaded_server(self, cfg, trace_writer=None):
        srv = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=1),
        )
        ingest = IngestServer(srv)
        gen = LoadGen(
            cfg, _sensor_chunks(0, n_frames=16), ingest,
            trace_writer=trace_writer,
        )
        summary = gen.run()
        return srv, ingest, summary

    def test_loadgen_trace_replays_bit_exactly(self, tmp_path):
        """The load generator's interleaved multi-stream traffic,
        recorded via ``trace_writer``, replays through a fresh server
        to the identical admissions, NACKs, and per-stream state."""
        cfg = LoadConfig(
            seed=3, ticks=8, arrival_rate=1.0,
            session_len_mu=1.0, session_len_sigma=0.5,
            burst_factor=2.0, burst_every=4,
        )
        path = os.path.join(tmp_path, "load.wtrace")
        with trace.TraceWriter(path) as w:
            srv1, ingest1, summary = self._loaded_server(cfg, w)
        assert w.n_records == summary["n_frames_sent"] + (
            summary["n_arrivals"] + summary["n_closed"]
        )

        srv2 = StreamServer(
            api.EPICCompressor(_ecfg()),
            ServerConfig(capacity=2, chunk_frames=CHUNK, queue_depth=1),
        )
        ingest2 = IngestServer(srv2)
        loop2 = Loopback(ingest2)
        fired = []
        trace.replay(
            path, loop2.send,
            on_advance=lambda: fired.append(ingest2.tick()),
        )
        # ticks with no traffic leave no records; make the totals match
        for _ in range(cfg.ticks - len(fired)):
            ingest2.tick()

        c1, c2 = ingest1.counters(), ingest2.counters()
        assert c1 == c2
        assert srv1.server_counters() == srv2.server_counters()
        assert sorted(srv1.live_sessions) == sorted(srv2.live_sessions)
        for sid in srv1.live_sessions:
            _assert_tree_bitwise(
                srv1.state(sid), srv2.state(sid), f"replayed stream {sid}"
            )


class TestWireClientTimeout:
    def test_wedged_server_surfaces_as_retriable_connection_error(self):
        import socket as _socket
        import threading as _threading

        srv_sock = _socket.socket()
        try:
            srv_sock.bind(("127.0.0.1", 0))
        except (OSError, PermissionError) as e:  # pragma: no cover
            pytest.skip(f"cannot bind local TCP socket: {e}")
        srv_sock.listen(1)
        host, port = srv_sock.getsockname()
        accepted = []
        t = _threading.Thread(  # accept, read nothing, never reply
            target=lambda: accepted.append(srv_sock.accept()),
            daemon=True,
        )
        t.start()
        try:
            client = WireClient(host, port, timeout=0.3)
            with pytest.raises(ConnectionError, match="unresponsive"):
                client.send(codec.encode_control(codec.OP_OPEN, 1))
            assert client.n_timeouts == 1
            # the poisoned socket was closed: a fresh send fails fast
            # instead of hanging (reconnect() is the recovery path)
            with pytest.raises(OSError):
                client.send(codec.encode_control(codec.OP_OPEN, 1))
        finally:
            for conn, _ in accepted:
                conn.close()
            srv_sock.close()
            t.join(timeout=2)


# ---------------------------------------------------------------------------
# Lock scope: data frames during a tick's step; control frames after it


class _Call:
    """Run ``fn`` on a daemon thread; ``done`` is set when it returns."""

    def __init__(self, fn):
        self.done = threading.Event()
        self.result = None
        self.error = None

        def run():
            try:
                self.result = fn()
            except BaseException as e:  # re-raised by join()
                self.error = e
            finally:
                self.done.set()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def join(self, timeout=60.0):
        self.thread.join(timeout)
        assert not self.thread.is_alive(), "the call did not return"
        if self.error is not None:
            raise self.error
        return self.result


def _hold_step(srv):
    """Make the next ``tick_step`` wait for ``release``; ``entered`` is
    set once a tick is inside its step phase."""
    entered, release = threading.Event(), threading.Event()
    orig = srv.tick_step

    def held(ready):
        entered.set()
        assert release.wait(timeout=60)
        return orig(ready)

    srv.tick_step = held
    return entered, release


class TestLockScope:
    def _wire_server(self, capacity=4, k_ladder=None, queue_depth=2, **kw):
        srv = StreamServer(
            api.EPICCompressor(_ecfg(prefilter_k=8 if k_ladder else 0)),
            ServerConfig(
                capacity=capacity, chunk_frames=CHUNK,
                queue_depth=queue_depth, k_ladder=k_ladder, **kw,
            ),
        )
        ingest = IngestServer(srv)
        return srv, ingest, Loopback(ingest)

    def _warm(self, ingest, loop, sid, chunk):
        """Open ``sid`` and serve one chunk (compiles the step)."""
        assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
        assert loop.send(codec.encode_chunk(
            chunk, stream_id=sid, seq=0, timestamp_ns=0
        )).ok
        assert ingest.tick() == [sid]

    def test_data_frame_acked_and_queued_during_step(self):
        srv, ingest, loop = self._wire_server()
        chunks = _sensor_chunks(0, n_frames=24)
        self._warm(ingest, loop, 1, chunks[0])
        assert loop.send(codec.encode_chunk(
            chunks[1], stream_id=1, seq=1, timestamp_ns=1
        )).ok
        entered, release = _hold_step(srv)
        try:
            tick = _Call(ingest.tick)
            assert entered.wait(timeout=30)
            # the tick popped seq 1 and is stepping it: seq 2 goes in
            send = _Call(lambda: loop.send(codec.encode_chunk(
                chunks[2], stream_id=1, seq=2, timestamp_ns=2
            )))
            assert send.done.wait(timeout=30), "the ACK waited for the step"
            assert send.join().ok
            assert not tick.done.is_set()
            assert len(srv._queues[1]) == 1
            assert ingest.counters()["n_frames_during_step"] == 1
        finally:
            release.set()
        assert tick.join() == [1]
        assert ingest.tick() == [1]  # the queued frame serves next
        assert srv.telemetry(1).n_chunks == 3
        assert srv.metrics.value("wire_frames_during_step_total") == 1
        assert loop.status()["wire_counters"]["n_frames_during_step"] == 1

    def test_control_frames_and_snapshot_wait_for_the_step(self):
        from repro.serve.checkpoint import snapshot_server

        srv, ingest, loop = self._wire_server()
        chunks = _sensor_chunks(0, n_frames=16)
        self._warm(ingest, loop, 1, chunks[0])
        assert loop.send(codec.encode_control(codec.OP_OPEN, 3)).ok
        assert loop.send(codec.encode_chunk(
            chunks[1], stream_id=1, seq=1, timestamp_ns=1
        )).ok
        ticks_before = srv.n_ticks
        entered, release = _hold_step(srv)
        try:
            tick = _Call(ingest.tick)
            assert entered.wait(timeout=30)
            calls = {
                "open": _Call(lambda: loop.send(
                    codec.encode_control(codec.OP_OPEN, 2)
                )),
                "close": _Call(lambda: loop.send(
                    codec.encode_control(codec.OP_CLOSE, 3)
                )),
                "status": _Call(loop.status),
                "snapshot": _Call(
                    lambda: snapshot_server(srv, ingest=ingest)
                ),
            }
            for name, call in calls.items():
                assert not call.done.wait(timeout=0.2), (
                    f"{name} ran during the step"
                )
            assert srv.live_sessions == [1, 3]
        finally:
            release.set()
        assert tick.join() == [1]
        assert calls["open"].join().ok
        assert calls["close"].join().ok
        status = calls["status"].join()
        _, meta = calls["snapshot"].join()
        # each saw the tick's step completed
        assert status["tick"] == ticks_before + 1
        assert meta["counters"]["n_ticks"] == ticks_before + 1
        assert sorted(srv.live_sessions) == [1, 2]

    def test_threaded_sender_and_ticker_match_sequential_serving(self):
        ladder = (8, 16, 32)
        sids = (1, 2, 3, 4)
        feeds = {
            sid: _sensor_chunks(sid, n_frames=32, n_obj=3 + sid % 3)
            for sid in sids
        }
        srv, ingest, loop = self._wire_server(k_ladder=ladder)
        for sid in sids:
            assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
        sent = threading.Event()
        deadline = time.monotonic() + 240

        def sender():
            nxt = {sid: 0 for sid in sids}
            while any(nxt[s] < len(feeds[s]) for s in sids):
                assert time.monotonic() < deadline, "sender timed out"
                progress = False
                for sid in sids:
                    seq = nxt[sid]
                    if seq == len(feeds[sid]):
                        continue
                    r = loop.send(codec.encode_chunk(
                        feeds[sid][seq], stream_id=sid, seq=seq,
                        timestamp_ns=seq,
                    ))
                    if r.ok:
                        nxt[sid] += 1
                        progress = True
                    else:
                        assert r.status_name == "backpressure", r
                if not progress:
                    sent.wait(timeout=0.001)
            sent.set()

        def ticker():
            while not (
                sent.is_set()
                and not any(len(q) for q in srv._queues.values())
            ):
                assert time.monotonic() < deadline, "ticker timed out"
                ingest.tick()

        calls = [_Call(sender), _Call(ticker)]
        for call in calls:
            call.join(timeout=300)

        seq_srv = StreamServer(
            api.EPICCompressor(_ecfg(prefilter_k=8)),
            ServerConfig(
                capacity=4, chunk_frames=CHUNK, queue_depth=2,
                k_ladder=ladder,
            ),
        )
        for sid in sids:
            seq_srv.admit(sid)
        for sid in sids:
            for c in feeds[sid]:
                assert seq_srv.submit(sid, c)
                assert seq_srv.tick() == [sid]
        for sid in sids:
            got, want = srv.telemetry(sid), seq_srv.telemetry(sid)
            for field in ("n_chunks", "n_frames", "n_processed",
                          "n_inserted", "buffer_valid"):
                assert getattr(got, field) == getattr(want, field), field
            assert list(got.k_trajectory) == list(want.k_trajectory)
            _assert_tree_bitwise(
                srv.state(sid), seq_srv.state(sid), f"stream {sid}"
            )
        assert ingest.counters()["n_frames_in"] == sum(
            len(f) for f in feeds.values()
        )

    def test_frames_during_step_stays_zero_single_threaded(self):
        srv, ingest, loop = self._wire_server()
        chunks = _sensor_chunks(0, n_frames=24)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        for seq, c in enumerate(chunks):
            assert loop.send(codec.encode_chunk(
                c, stream_id=1, seq=seq, timestamp_ns=seq
            )).ok
            ingest.tick()
        c = ingest.counters()
        assert (c["n_frames_in"], c["n_frames_during_step"]) == (3, 0)
        assert loop.status()["wire_counters"]["n_frames_during_step"] == 0

    def test_idle_eviction_keeps_a_frame_acked_during_the_step(self):
        # idle_frames=CHUNK: a stream that does not step in a tick is
        # idle at its end.  Stream 2 steps nothing in the held tick, but
        # a frame of it is ACKed during the step: it must be served,
        # not dropped with an evicted stream.
        srv, ingest, loop = self._wire_server(
            eviction="idle", idle_frames=CHUNK
        )
        chunks = _sensor_chunks(0, n_frames=24)
        for sid in (1, 2):
            assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
            assert loop.send(codec.encode_chunk(
                chunks[0], stream_id=sid, seq=0, timestamp_ns=0
            )).ok
        assert sorted(ingest.tick()) == [1, 2]
        assert loop.send(codec.encode_chunk(
            chunks[1], stream_id=1, seq=1, timestamp_ns=1
        )).ok
        entered, release = _hold_step(srv)
        try:
            tick = _Call(ingest.tick)
            assert entered.wait(timeout=30)
            send = _Call(lambda: loop.send(codec.encode_chunk(
                chunks[1], stream_id=2, seq=1, timestamp_ns=1
            )))
            reply = send.join(timeout=30)
        finally:
            release.set()
        assert tick.join() == [1]
        assert reply.ok
        assert 2 in srv.live_sessions and len(srv._queues[2]) == 1
        assert ingest.tick() == [2]  # served; stream 1 idles out
        assert srv.live_sessions == [2]
        assert srv.telemetry(2).n_chunks == 2
        assert srv.server_counters()["n_dropped"] == 0

    def test_frames_for_a_stream_evicted_before_the_prune(self):
        # The stream server evicts on its own (a bare tick, so the wire
        # frontier has not pruned yet): every frame naming the stream
        # NACKs unknown_stream, and the session is forgotten.
        srv, ingest, loop = self._wire_server(
            eviction="idle", idle_frames=CHUNK
        )
        chunk = _sensor_chunks(0)[0]
        for sid in (1, 2, 3):
            assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
        srv.tick()
        assert srv.live_sessions == [] and sorted(ingest._seq_seen) == [
            1, 2, 3,
        ]
        r = loop.send(codec.encode_chunk(
            chunk, stream_id=1, seq=0, timestamp_ns=0
        ))
        assert r.status_name == "unknown_stream"
        for msg in (
            codec.encode_control(codec.OP_CLOSE, 2),
            codec.encode_credit(3, 1),
        ):
            assert loop.send(msg).status_name == "unknown_stream"
        assert ingest._seq_seen == {}
        # the stream may be opened again
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
