"""Fault-tolerant serving: live-slot checkpoint/restore + crash soak.

Covers the `repro.serve.checkpoint` contract — snapshot a running
StreamServer (device slot states, generations, controllers, queued
chunks, scheduler costs, wire cursors), restore into a *fresh* process,
and resume serving bit-identically — plus the kill→restore→replay soak
with deterministic FailureInjector crash points (mid-tick, mid-save,
mid-migration, mid-wire-frame).  The soak's acceptance bar: per-stream
outputs and ``k_trajectory`` bitwise equal to an uninterrupted run, and
zero post-restore retraces (every pool step variant compiled exactly
once in the restored process)."""

import os

import jax
import numpy as np
import pytest

from repro import api
from repro.checkpoint import store
from repro.core import pipeline as P
from repro.data import synthetic as SYN
from repro.runtime import fault
from repro.serve import ServerConfig, StreamServer
from repro.serve.checkpoint import (
    SERVE_SCHEMA,
    ServeCheckpointer,
    restore_server,
    save_server,
    snapshot_server,
)
from repro.serve.slots import StaleSlotError
from repro.wire import codec
from repro.wire.server import IngestServer, Loopback, ResumableSession

FRAME = 64
PATCH = 16
CHUNK = 8
LADDER = (8, 16, 32)
N_STREAMS = 3
N_ROUNDS = 5


def _ecfg(**kw):
    base = dict(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=32,
        tau=0.10, gamma=0.015, theta=8, window=16,
    )
    base.update(kw)
    return P.EPICConfig(**base)


def _comp(k=8):
    return api.EPICCompressor(_ecfg(prefilter_k=k))


def _chunks(seed, n_frames=48):
    scfg = SYN.StreamConfig(n_frames=n_frames, hw=(FRAME, FRAME), n_obj=4)
    s, _ = SYN.generate_stream(jax.random.PRNGKey(seed), scfg)
    stream = api.SensorChunk(s.frames, s.poses, s.gazes, s.depth)
    return list(api.iter_chunks(stream, CHUNK, remainder="drop"))


def _server_cfg(tiers=None, k_ladder=LADDER, **kw):
    return ServerConfig(
        capacity=4, chunk_frames=CHUNK, queue_depth=2,
        k_ladder=k_ladder, tiers=tiers, **kw,
    )


def _assert_tree_bitwise(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{msg} leaf {i}"
        )


# ---------------------------------------------------------------------------
# Snapshot / restore roundtrips


class TestSnapshotRestore:
    @pytest.mark.parametrize(
        "tiers,k_ladder",
        [(None, None), (None, LADDER), ((2, 2), LADDER)],
        ids=["flat", "adaptive", "tiered"],
    )
    def test_roundtrip_bitwise(self, tmp_path, tiers, k_ladder):
        """Save a live server mid-run (queued chunks on board), restore
        fresh, finish serving: states + k_trajectory bitwise equal to
        the uninterrupted server."""
        chunks = {sid: _chunks(sid) for sid in (1, 2, 3)}

        def build():
            srv = StreamServer(
                _comp(8 if k_ladder else 0),
                _server_cfg(tiers=tiers, k_ladder=k_ladder),
            )
            for sid in chunks:
                srv.admit(sid)
            for i in range(2):
                for sid in chunks:
                    assert srv.submit(sid, chunks[sid][i])
                srv.tick()
            # leave one chunk pending in each queue at snapshot time
            for sid in chunks:
                assert srv.submit(sid, chunks[sid][2])
            return srv

        ref = build()
        ref.tick()
        for sid in chunks:
            assert ref.submit(sid, chunks[sid][3])
        ref.tick()

        srv = build()
        save_server(str(tmp_path), srv.n_ticks, srv)
        srv2, ingest, step = restore_server(
            str(tmp_path), _comp(8 if k_ladder else 0)
        )
        assert step == 2 and ingest is None
        assert srv2.live_sessions == list(chunks)
        assert all(len(q) == 1 for q in srv2._queues.values())
        srv2.tick()  # serves the restored queue contents
        for sid in chunks:
            assert srv2.submit(sid, chunks[sid][3])
        srv2.tick()

        for sid in chunks:
            _assert_tree_bitwise(
                ref.state(sid), srv2.state(sid), f"stream {sid}"
            )
            assert (
                ref.telemetry(sid).k_trajectory
                == srv2.telemetry(sid).k_trajectory
            )
        assert srv2.n_ticks == ref.n_ticks
        # one compile per variant in the restored process: restore
        # itself never traces a pool program
        assert all(v == 1 for v in srv2.step_cache_sizes().values())

    @pytest.mark.parametrize("tiers", [None, (2, 2)], ids=["flat", "tiered"])
    def test_snapshot_survives_donated_steps(self, tiers):
        """With donation on (the accelerator default), the ticks after a
        snapshot consume the live state buffers; the snapshot owns its
        copy and still reads back the state it captured."""
        chunks = _chunks(7)

        def serve(donate, n_ticks):
            srv = StreamServer(
                _comp(8), _server_cfg(tiers=tiers), donate=donate
            )
            srv.admit(1)
            srv.admit(2)
            for i in range(n_ticks):
                for sid in (1, 2):
                    assert srv.submit(sid, chunks[i])
                srv.tick()
            return srv, srv.pool.tiers if tiers else [srv.pool]

        # What the snapshot must hold, from a twin that never donates (a
        # host read of a live CPU buffer would pin it against donation).
        _, twin = serve(False, 2)
        want = [p.states for p in twin]
        srv, pools = serve(True, 2)
        tree, _ = snapshot_server(srv)
        live = jax.tree.leaves([p.states for p in pools])
        for i in range(2, 4):
            for sid in (1, 2):
                assert srv.submit(sid, chunks[i])
            srv.tick()
        assert any(x.is_deleted() for x in live)  # the steps donated
        _assert_tree_bitwise(tree["tiers"], want, "snapshot")

    def test_counters_and_evicted_survive(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        chunks = _chunks(5)
        srv.admit(1)
        srv.admit(2)
        for i in range(2):
            srv.submit(1, chunks[i])
            srv.tick()
        srv.close(2)
        save_server(str(tmp_path), srv.n_ticks, srv)
        srv2, _, _ = restore_server(str(tmp_path), _comp(0))
        assert srv2.server_counters() == srv.server_counters()
        assert [t.session_id for t in srv2.evicted] == [2]
        assert srv2._sched.cost_estimates() == srv._sched.cost_estimates()

    def test_restore_into_provided_prewarmed_server(self, tmp_path):
        cfg = _server_cfg(k_ladder=None, prewarm=True)
        srv = StreamServer(_comp(0), cfg)
        chunks = _chunks(7)
        srv.admit(1)
        srv.submit(1, chunks[0])
        srv.tick()
        save_server(str(tmp_path), srv.n_ticks, srv)
        target = StreamServer(_comp(0), cfg)
        srv2, _, _ = restore_server(str(tmp_path), _comp(0), server=target)
        assert srv2 is target
        _assert_tree_bitwise(srv.state(1), srv2.state(1))

    def test_provided_server_fences(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        srv.admit(1)
        save_server(str(tmp_path), 0, srv)
        other_cfg = StreamServer(
            _comp(0),
            _server_cfg(k_ladder=None)._replace(queue_depth=3),
        )
        with pytest.raises(ValueError, match="config"):
            restore_server(str(tmp_path), _comp(0), server=other_cfg)
        busy = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        busy.admit(9)
        with pytest.raises(ValueError, match="live sessions"):
            restore_server(str(tmp_path), _comp(0), server=busy)

    def test_compressor_fence(self, tmp_path):
        srv = StreamServer(_comp(8), _server_cfg())
        srv.admit(1)
        save_server(str(tmp_path), 0, srv)
        with pytest.raises(ValueError, match="compressor mismatch"):
            restore_server(str(tmp_path), _comp(16))

    def test_generation_fenced_restore(self, tmp_path):
        """Generation counters survive verbatim: a handle minted before
        the crash stays valid after restore, and a stale one still
        raises."""
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        chunks = _chunks(3)
        srv.admit(1)
        srv.close(1)
        srv.admit(1)  # generation bumped twice on this slot
        srv.submit(1, chunks[0])
        srv.tick()
        tier, local = srv._locate(1)
        gen = srv._tier_pool(tier).generation_of(local)
        save_server(str(tmp_path), srv.n_ticks, srv)
        srv2, _, _ = restore_server(str(tmp_path), _comp(0))
        pool2 = srv2._tier_pool(tier)
        pool2.slot_state(local, expect_generation=gen)  # still valid
        with pytest.raises(StaleSlotError):
            pool2.slot_state(local, expect_generation=gen - 1)

    def test_non_serve_checkpoint_refused(self, tmp_path):
        store.save(str(tmp_path), 1, {"w": np.zeros((3,))})
        with pytest.raises(ValueError, match="serve"):
            restore_server(str(tmp_path), _comp(0), step=1)

    def test_restore_falls_back_past_damaged_newest(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        chunks = _chunks(2)
        srv.admit(1)
        srv.submit(1, chunks[0])
        srv.tick()
        save_server(str(tmp_path), 1, srv)
        srv.submit(1, chunks[1])
        srv.tick()
        save_server(str(tmp_path), 2, srv)
        # crash-truncated newest step: manifest survived, a shard didn't
        os.unlink(tmp_path / "step_00000002" / "shard_0.npz")
        srv2, _, step = restore_server(str(tmp_path), _comp(0))
        assert step == 1
        assert srv2.n_ticks == 1

    def test_snapshot_requires_matching_ingest(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        other = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        with pytest.raises(ValueError, match="different StreamServer"):
            snapshot_server(srv, ingest=IngestServer(other))

    def test_wire_cursors_roundtrip(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        ingest = IngestServer(srv, strict_seq=True)
        loop = Loopback(ingest)
        chunks = _chunks(11)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 4)).ok
        for seq in range(2):
            assert loop.send(codec.encode_chunk(
                chunks[seq], stream_id=4, seq=seq, timestamp_ns=0,
            )).ok
            ingest.tick()
        save_server(str(tmp_path), srv.n_ticks, srv, ingest=ingest)
        _, ing2, _ = restore_server(
            str(tmp_path), _comp(0), with_ingest=True
        )
        assert ing2.strict_seq and ing2._seq_seen == {4: 1}
        assert ing2.counters()["n_frames_in"] == 2
        # the restored cursor refuses a replayed duplicate like the
        # original would
        reply = codec.decode_reply(ing2.handle_message(codec.encode_chunk(
            chunks[0], stream_id=4, seq=1, timestamp_ns=0,
        )))
        assert reply.status == codec.NACK_OUT_OF_ORDER

    def test_frames_during_step_counter_roundtrips(
        self, tmp_path, monkeypatch
    ):
        from repro.serve import checkpoint

        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        ingest = IngestServer(srv)
        ingest.n_frames_in = 9
        ingest.n_frames_during_step = 7
        save_server(str(tmp_path / "new"), 1, srv, ingest=ingest)
        _, ing2, _ = restore_server(
            str(tmp_path / "new"), _comp(0), with_ingest=True
        )
        assert ing2.counters()["n_frames_during_step"] == 7
        # a checkpoint written before the counter existed restores it 0
        monkeypatch.setattr(
            checkpoint, "_WIRE_COUNTER_ATTRS",
            tuple(a for a in checkpoint._WIRE_COUNTER_ATTRS
                  if a != "n_frames_during_step"),
        )
        save_server(str(tmp_path / "old"), 1, srv, ingest=ingest)
        monkeypatch.undo()
        _, ing3, _ = restore_server(
            str(tmp_path / "old"), _comp(0), with_ingest=True
        )
        assert ing3.counters()["n_frames_in"] == 9
        assert ing3.counters()["n_frames_during_step"] == 0


# ---------------------------------------------------------------------------
# Checkpointer cadence


class TestServeCheckpointer:
    def test_cadence_and_gc(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        chunks = _chunks(9, n_frames=96)
        srv.admit(1)
        ckpt = ServeCheckpointer(
            str(tmp_path), srv, every_ticks=2, keep=2
        )
        saves = 0
        for i in range(7):
            srv.submit(1, chunks[i])
            srv.tick()
            saves += ckpt.maybe_save()
            assert not ckpt.maybe_save()  # idempotent within a tick
        ckpt.wait()
        assert saves == 3 and ckpt.n_saves == 3
        assert store.complete_steps(str(tmp_path)) == [4, 6]  # keep=2

    def test_every_ticks_validated(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        with pytest.raises(ValueError, match="every_ticks"):
            ServeCheckpointer(str(tmp_path), srv, every_ticks=0)

    def test_restore_waits_for_inflight_save(self, tmp_path):
        srv = StreamServer(_comp(0), _server_cfg(k_ladder=None))
        srv.admit(1)
        srv.submit(1, _chunks(1)[0])
        srv.tick()
        ckpt = ServeCheckpointer(str(tmp_path), srv, every_ticks=1)
        ckpt.save_now()  # async write possibly still in flight
        srv2, _, step = ckpt.restore(_comp(0))
        assert step == 1 and srv2.live_sessions == [1]


# ---------------------------------------------------------------------------
# The crash/fault-injection soak


class _FlakyTransport:
    """Loopback wrapper that can die mid-wire-frame: before delivering
    a data frame it consults the injector with ``("wire", sid, seq)`` —
    a fired point crashes the 'process' with the frame unacked (it
    stays in the client's window for post-restore replay)."""

    def __init__(self, loop, injector):
        self.loop = loop
        self.inj = injector

    def send(self, msg):
        if self.inj is not None:
            kind, frame = codec.decode_message(msg)
            if kind == "data":
                self.inj.maybe_fail(("wire", frame.stream_id, frame.seq))
        return self.loop.send(msg)


def _run_reference():
    """The uninterrupted run: per-stream final states + k_trajectory."""
    return _run_soak(None, [], tiers=None)


def _run_soak(tmp_path, fail_at, *, tiers=None, damage_newest=False):
    """Drive N_STREAMS through N_ROUNDS of send+tick with checkpoints
    every 2 ticks; any injected WorkerFailure 'kills the process'
    (server, ingest, checkpointer all dropped on the floor), restores
    into fresh objects, RESUMEs every client session, and carries on.
    Returns per-stream final states, k trajectories, and the final
    server for extra assertions."""
    inj = fault.FailureInjector(fail_at)
    chunks = {sid: _chunks(sid) for sid in range(1, N_STREAMS + 1)}

    srv = StreamServer(_comp(8), _server_cfg(tiers=tiers))
    ingest = IngestServer(srv)
    ckpt = (
        ServeCheckpointer(str(tmp_path), srv, every_ticks=2, ingest=ingest)
        if tmp_path is not None
        else None
    )
    loop = Loopback(ingest)
    sess = {
        sid: ResumableSession(
            _FlakyTransport(loop, inj), sid, drain=ingest.tick
        )
        for sid in chunks
    }
    for s in sess.values():
        assert s.open().ok

    pos = {sid: 0 for sid in chunks}  # next chunk index per stream
    i = 0
    n_crashes = 0
    while i < N_ROUNDS:
        try:
            for sid, s in sess.items():
                if pos[sid] == i:
                    pos[sid] = i + 1
                    s.send_chunk(chunks[sid][i])
            inj.maybe_fail(("mid_tick", i))
            ingest.tick()
            if ckpt is not None:
                ckpt.maybe_save()
            inj.maybe_fail(("post_tick", i))
            i += 1
        except fault.WorkerFailure:
            assert ckpt is not None, "crash injected without a checkpointer"
            n_crashes += 1
            # -- the process dies here --------------------------------
            ckpt.wait()  # the dying writer's last save lands or not;
            if damage_newest:
                # simulate dying *mid-save* instead: the newest step is
                # a partial write (no manifest) plus tmp debris
                newest = store.latest_step(str(tmp_path))
                part = tmp_path / f"step_{newest + 1:08d}"
                part.mkdir()
                (part / "shard_0.npz").write_bytes(b"partial write")
                tmp = tmp_path / f"step_{newest + 2:08d}.tmp"
                tmp.mkdir()
                (tmp / "shard_0.npz").write_bytes(b"crashed")
            # -- a fresh process restores ------------------------------
            srv, ingest, _step = restore_server(
                str(tmp_path), _comp(8), with_ingest=True
            )
            ckpt = ServeCheckpointer(
                str(tmp_path), srv, every_ticks=2, ingest=ingest
            )
            loop = Loopback(ingest)
            for s in sess.values():
                s.transport = _FlakyTransport(loop, inj)
                s.drain = ingest.tick
                s.resume()  # replay everything past the restored cursor
    while any(len(q) for q in srv._queues.values()):
        ingest.tick()
    if ckpt is not None:
        # An async save may still be in flight; its step_*.tmp must not
        # be mistaken for crash debris by the cleanup assertions.
        ckpt.wait()
    states = {
        sid: jax.tree.map(np.asarray, srv.state(sid)) for sid in chunks
    }
    trajs = {
        sid: list(srv.telemetry(sid).k_trajectory) for sid in chunks
    }
    return states, trajs, srv, n_crashes


class TestCrashSoak:
    @pytest.fixture(scope="class")
    def reference(self):
        states, trajs, _, _ = _run_soak(None, [], tiers=None)
        return states, trajs

    @pytest.mark.parametrize(
        "fail_at,damage_newest",
        [
            ([("mid_tick", 2)], False),
            ([("post_tick", 2)], True),
            ([("wire", 2, 3)], False),
            ([("mid_tick", 2), ("wire", 3, 4)], False),
        ],
        ids=["mid_tick", "mid_save", "mid_wire_frame", "double_crash"],
    )
    def test_bit_exact_recovery(
        self, tmp_path, reference, fail_at, damage_newest
    ):
        ref_states, ref_trajs = reference
        states, trajs, srv, n_crashes = _run_soak(
            tmp_path, fail_at, damage_newest=damage_newest
        )
        assert n_crashes == len(fail_at)
        for sid in ref_states:
            _assert_tree_bitwise(
                ref_states[sid], states[sid], f"stream {sid}"
            )
            assert ref_trajs[sid] == trajs[sid], f"stream {sid}"
        # zero post-restore retraces: every variant compiled once in
        # the final (restored) process
        assert all(v == 1 for v in srv.step_cache_sizes().values())
        # mid-save debris never survives a later completed save
        assert not [
            n for n in os.listdir(tmp_path) if n.endswith(".tmp")
        ]

    def test_mid_migration_crash(self, tmp_path, reference):
        """Tiered pool: crash after a tick whose rebalance migrated a
        stream; restore re-binds the tiered placement verbatim and the
        run stays bitwise identical to the *flat* reference (the tiered
        == flat contract composes with crash/restore)."""
        ref_states, ref_trajs = reference
        states, trajs, srv, n_crashes = _run_soak(
            tmp_path, [("post_tick", 2)], tiers=(2, 2)
        )
        assert n_crashes == 1
        assert srv._tiered and srv.pool.n_migrations >= 1
        for sid in ref_states:
            _assert_tree_bitwise(
                ref_states[sid], states[sid], f"stream {sid}"
            )
            assert ref_trajs[sid] == trajs[sid], f"stream {sid}"
        assert all(v == 1 for v in srv.step_cache_sizes().values())
