"""repro.obs: metrics registry, flight recorder, STATUS introspection.

The observability contract of PR 10 (ROADMAP "tier-1"):

* one :class:`~repro.obs.metrics.MetricsRegistry` backs every counter
  view — ``IngestServer.counters()``, ``StreamServer.server_counters``
  and the registry snapshot must agree because they read the *same*
  cells (checked here after a mixed loss/overload soak, not just on a
  happy path);
* histogram percentiles are ``nan`` on empty (never a crash) and merge
  refuses layout mismatches;
* the :class:`~repro.obs.trace.FlightRecorder` ring is bounded, its
  Chrome-trace dump is valid (pinned against an injected fake clock),
  and the serving tick leaves the documented phase spans + events;
* the wire ``STATUS`` frame returns exactly what host-side
  :func:`~repro.obs.status.collect_status` computes — over loopback
  and over a real TCP socket;
* ``k_trajectory_limit`` bounds the per-stream rung history without
  changing the decision rule;
* :class:`~repro.runtime.fault.FailureInjector` kill points leave
  post-mortem flight dumps.
"""

from __future__ import annotations

import json
import math
import os

import jax
import pytest

from repro import api
from repro.core import pipeline as P
from repro.data import synthetic as SYN
from repro.obs import dump as obs_dump
from repro.obs.metrics import (
    DEFAULT_HI,
    DEFAULT_LO,
    DEFAULT_N_BUCKETS,
    Histogram,
    MetricsRegistry,
    counter_property,
    gauge_property,
)
from repro.obs.status import collect_status
from repro.obs.trace import NULL_SPAN, FlightRecorder, self_seconds
from repro.runtime.fault import FailureInjector, WorkerFailure
from repro.serve import ServerConfig, StreamServer
from repro.serve.adaptive import KLadderController
from repro.serve.degrade import DegradeConfig, DegradeController
from repro.wire import codec
from repro.wire.latency import LatencyHistogram, LatencyRecorder
from repro.wire.loadgen import LoadConfig, LoadGen
from repro.wire.server import IngestServer, Loopback, WireClient

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


def _server(**cfg_kw):
    base = dict(capacity=2, chunk_frames=CHUNK, queue_depth=2)
    base.update(cfg_kw)
    return StreamServer(api.EPICCompressor(_ecfg()), ServerConfig(**base))


# ---------------------------------------------------------------------------
# MetricsRegistry: typed cells, labels, kinds, export
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_get_or_create_is_stable(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total")
        c.inc()
        c.inc(4)
        assert reg.counter("requests_total") is c
        assert reg.value("requests_total") == 5

    def test_labels_address_distinct_cells(self):
        reg = MetricsRegistry()
        reg.counter("nacks_total", status="backpressure").inc(3)
        reg.counter("nacks_total", status="bad_crc").inc()
        fam = reg.family("nacks_total")
        assert {dict(lk)["status"]: m.value for lk, m in fam.items()} == {
            "backpressure": 3, "bad_crc": 1,
        }
        # label order never matters
        reg.counter("multi", a=1, b=2).inc()
        assert reg.counter("multi", b=2, a=1).value == 1

    def test_one_kind_per_name(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="is a counter"):
            reg.gauge("x")
        with pytest.raises(TypeError, match="is a counter"):
            reg.histogram("x", phase="q")  # even under fresh labels

    def test_name_and_label_validation(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("bad name")
        with pytest.raises(ValueError, match="invalid label name"):
            reg.counter("ok", **{"bad-label": 1})

    def test_computed_gauge_reads_live_and_rejects_set(self):
        reg = MetricsRegistry()
        state = {"v": 1}
        g = reg.gauge("live", fn=lambda: state["v"])
        assert g.value == 1
        state["v"] = 7
        assert reg.value("live") == 7
        with pytest.raises(TypeError, match="computed gauge"):
            g.set(0)

    def test_clear_family_keeps_the_kind(self):
        reg = MetricsRegistry()
        reg.counter("gaps", stream=1).inc()
        reg.clear_family("gaps")
        assert reg.family("gaps") == {}
        with pytest.raises(TypeError):
            reg.gauge("gaps")  # the name is still a counter

    def test_value_raises_on_unknown(self):
        with pytest.raises(KeyError):
            MetricsRegistry().value("nope")

    def test_snapshot_is_json_safe(self):
        reg = MetricsRegistry()
        reg.counter("c", kind="a").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("h").record(0.01)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["c"]["values"] == [
            {"labels": {"kind": "a"}, "value": 2}
        ]
        assert snap["g"]["values"][0]["value"] == 1.5
        assert snap["h"]["values"][0]["count"] == 1

    def test_merge_counters_add_gauges_take_histograms_fold(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("c").inc(1)
        b.counter("c").inc(2)
        b.gauge("g").set(9)
        a.gauge("live", fn=lambda: 42)
        b.gauge("live", fn=lambda: 0)  # other's computed: ignored
        a.histogram("h").record(0.001)
        b.histogram("h").record(0.002)
        a.merge(b)
        assert a.counter("c").value == 3
        assert a.gauge("g").value == 9
        assert a.gauge("live").value == 42
        assert a.histogram("h").n == 2

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("frames_total", tier=0).inc(5)
        reg.gauge("level").set(2)
        reg.histogram("lat", n_buckets=4).record(0.01)
        text = reg.to_prometheus()
        assert "# TYPE frames_total counter" in text
        assert 'frames_total{tier="0"} 5' in text
        assert "level 2" in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text


# ---------------------------------------------------------------------------
# Histogram: empty-nan pin, interpolation, layout-checked merge
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_empty_percentile_is_nan_and_summary_none(self):
        h = Histogram()
        assert math.isnan(h.percentile(0.5))
        assert math.isnan(h.percentile(0.99))
        s = h.summary()
        assert s["count"] == 0 and s["p50_ms"] is None

    def test_single_sample_bounds(self):
        h = Histogram()
        h.record(0.010)
        for q in (0.5, 0.95, 0.99):
            p = h.percentile(q)
            assert 0 < p <= h.max_s
        assert h.summary()["count"] == 1

    def test_percentiles_are_monotone(self):
        h = Histogram()
        for i in range(1, 101):
            h.record(i * 1e-3)
        assert h.percentile(0.5) <= h.percentile(0.95) <= h.percentile(0.99)
        assert abs(h.percentile(0.5) - 0.050) < 0.010  # ~9% buckets
        assert h.max_s == pytest.approx(0.100)

    def test_merge_is_count_exact(self):
        a, b, both = Histogram(), Histogram(), Histogram()
        for i in range(50):
            a.record(i * 1e-3), both.record(i * 1e-3)
        for i in range(50, 100):
            b.record(i * 1e-3), both.record(i * 1e-3)
        a.merge(b)
        assert a.counts == both.counts
        assert a.n == both.n == 100
        assert a.percentile(0.95) == both.percentile(0.95)

    def test_merge_refuses_layout_mismatch(self):
        a = Histogram(n_buckets=8)
        for other in (
            Histogram(n_buckets=16),
            Histogram(lo=1e-3, n_buckets=8),
            Histogram(hi=60.0, n_buckets=8),
        ):
            with pytest.raises(ValueError, match="bucket layouts"):
                a.merge(other)

    def test_latency_histogram_shares_the_default_layout(self):
        assert LatencyHistogram().layout == (
            DEFAULT_LO, DEFAULT_HI, DEFAULT_N_BUCKETS
        )
        # so recorder merges across pools can never hit the mismatch path
        Histogram().merge(LatencyHistogram())

    def test_recorder_routes_through_a_shared_registry(self):
        reg = MetricsRegistry()
        rec = LatencyRecorder(metrics=reg)
        rec.observe(0.0, 0.5, 1.5)
        assert rec.n == 1
        fam = reg.family("ingest_latency_seconds")
        assert {dict(lk)["phase"] for lk in fam} == {
            "queue_wait", "service", "total"
        }
        assert reg.value(
            "ingest_latency_seconds", phase="total"
        )["count"] == 1


# ---------------------------------------------------------------------------
# counter_property / gauge_property: attribute views over registry cells
# ---------------------------------------------------------------------------


class _Instrumented:
    hits = counter_property("hits_total")
    level = gauge_property("level", cast=int)

    def __init__(self):
        self.metrics = MetricsRegistry()
        self.hits = 0
        self.level = 0


class TestAttributeViews:
    def test_read_modify_write_hits_the_cell(self):
        obj = _Instrumented()
        obj.hits += 1
        obj.hits += 2
        assert obj.hits == 3
        assert obj.metrics.counter("hits_total").value == 3
        obj.hits = 10  # checkpoint-restore style overwrite
        assert obj.metrics.value("hits_total") == 10

    def test_gauge_property_casts(self):
        obj = _Instrumented()
        obj.level = 2.9
        assert obj.level == 2
        assert obj.metrics.gauge("level").value == 2


# ---------------------------------------------------------------------------
# FlightRecorder: ring bound, clock-pinned Chrome trace, orphans
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class TestFlightRecorder:
    def test_ring_is_bounded_oldest_first(self):
        rec = FlightRecorder(capacity=3, clock=_FakeClock())
        for i in range(7):
            rec.begin_tick(i)
            rec.end_tick()
        ticks = rec.ticks()
        assert [t["tick"] for t in ticks] == [4, 5, 6]
        assert rec.n_ticks_recorded == 7

    def test_begin_tick_auto_closes_predecessor(self):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.begin_tick(0)
        rec.begin_tick(1)  # no end_tick(0)
        rec.end_tick()
        assert [t["tick"] for t in rec.ticks()] == [0, 1]

    def test_chrome_trace_is_pinned_against_the_clock(self):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.begin_tick(0)                      # t0 = 1
        with rec.span("dispatch"):             # 2 .. 3
            pass
        rec.event("admit", stream=7, slot=0)   # 4
        rec.end_tick()                         # t1 = 5
        doc = json.loads(json.dumps(rec.to_chrome_trace()))
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        tick = by_name["tick 0"]
        assert tick["ph"] == "X"
        assert (tick["ts"], tick["dur"]) == (1e6, 4e6)
        span = by_name["dispatch"]
        assert (span["ts"], span["dur"]) == (2e6, 1e6)
        admit = by_name["admit"]
        assert admit["ph"] == "i" and admit["ts"] == 4e6
        assert admit["args"] == {"stream": 7, "slot": 0, "tick": 0}
        assert doc["otherData"]["ticks_retained"] == 1

    def test_orphan_events_survive_without_an_open_tick(self):
        rec = FlightRecorder(capacity=2, clock=_FakeClock())
        rec.event("checkpoint", step=3)
        doc = rec.to_chrome_trace()
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "i"]
        assert names == ["checkpoint"]
        assert rec.n_events == 1

    def test_non_json_event_args_are_stringified_on_dump(self):
        rec = FlightRecorder(capacity=2, clock=_FakeClock())
        rec.begin_tick(0)
        rec.event("evict", stream=("sess", 3))
        rec.end_tick()
        doc = json.loads(json.dumps(rec.to_chrome_trace()))
        (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert ev["args"]["stream"] == "('sess', 3)"

    def test_dump_and_cli_summary(self, tmp_path):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.begin_tick(0)
        with rec.span("ingest"):
            pass
        rec.end_tick()
        path = rec.dump(str(tmp_path / "trace.json"))
        assert obs_dump.main([path]) == 0
        with open(path) as f:
            text = obs_dump.summarize(json.load(f))
        assert "ticks retained: 1" in text and "ingest" in text

    def test_summarize_rejects_non_traces(self):
        with pytest.raises(ValueError, match="no traceEvents"):
            obs_dump.summarize({"foo": 1})

    def test_null_span_and_capacity_validation(self):
        with NULL_SPAN:
            pass
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)


class TestChunkRecords:
    def test_chunk_ring_is_bounded_and_keyed(self):
        rec = FlightRecorder(capacity=3, clock=_FakeClock())
        for seq in range(10):
            rec.chunk_spans("s", seq, ("wire.decode", seq, seq + 0.5))
        chunks = rec.chunks()
        assert [c["seq"] for c in chunks] == [7, 8, 9]
        assert rec.n_chunks_recorded == 10
        assert len(rec._chunk_of) == 3  # the key index falls off too
        # a later span of a retained key joins its record; the pop
        # closes it and lists it in the open tick
        rec.begin_tick(0)
        rec.chunk_spans("s", 9, ("queue.wait", 10.0, 11.0), tick=0)
        rec.end_tick()
        (tick,) = rec.ticks()
        assert tick["chunks"] == [chunks[-1]]
        assert chunks[-1]["tick"] == 0
        assert [n for n, _, _ in chunks[-1]["spans"]] == [
            "wire.decode", "queue.wait",
        ]
        # a reused key after the pop (a reopened stream) is a new record
        rec.chunk_spans("s", 9, ("wire.decode", 12.0, 13.0))
        assert len(rec.chunks()) == 3 and rec.chunks()[-1]["tick"] is None

    def test_chunk_ring_under_concurrent_writers(self):
        """Socket threads record chunk spans while a tick thread pops:
        no record is lost from the counts, the ring stays bounded and
        the key index only names retained, unpopped records."""
        import sys
        import threading

        rec = FlightRecorder(capacity=64)
        n_writers, per_writer = 2 * (os.cpu_count() or 2), 300
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def write(w):
                for seq in range(per_writer):
                    rec.chunk_spans(w, seq, ("wire.decode", 0.0, 1.0))

            def pop():
                for t in range(per_writer):
                    rec.begin_tick(t)
                    for w in range(n_writers):
                        rec.chunk_spans(w, t, ("queue.wait", 1.0, 2.0), tick=t)
                    rec.end_tick()

            threads = [threading.Thread(target=write, args=(w,))
                       for w in range(n_writers)]
            threads.append(threading.Thread(target=pop))
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
        finally:
            sys.setswitchinterval(old)
        chunks = rec.chunks()
        assert len(chunks) == 64
        assert rec.n_spans == 2 * n_writers * per_writer
        ring = {id(c) for c in chunks}
        assert all(id(r) in ring and r["tick"] is None
                   for r in rec._chunk_of.values())

    def test_chunks_without_a_seq_never_merge(self):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.chunk_spans("s", None, ("queue.wait", 0.0, 1.0), tick=0)
        rec.chunk_spans("s", None, ("queue.wait", 1.0, 2.0), tick=1)
        assert len(rec.chunks()) == 2 and not rec._chunk_of

    def test_carried_span_opens_the_next_tick(self):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.carry_span("lock_wait", 0.25, 0.5)
        rec.begin_tick(0)
        rec.end_tick()
        rec.begin_tick(1)
        rec.end_tick()
        t0, t1 = rec.ticks()
        assert t0["spans"] == [("lock_wait", 0.25, 0.5)] and t1["spans"] == []

    def test_chunk_spans_are_dumped_on_their_own_track(self):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.chunk_spans(("sess", 1), 5, ("wire.decode", 1.0, 3.0))
        doc = json.loads(json.dumps(rec.to_chrome_trace()))
        (ev,) = [e for e in doc["traceEvents"] if e.get("cat") == "chunk"]
        assert (ev["name"], ev["ts"], ev["dur"], ev["tid"]) == (
            "wire.decode", 1e6, 2e6, 3,
        )
        assert ev["args"] == {"stream": "('sess', 1)", "seq": 5, "tick": None}
        assert doc["otherData"]["chunks_retained"] == 1

    def test_spans_on_profile_maps_each_tick_by_its_anchor(self):
        rec = FlightRecorder(capacity=4)
        rec.begin_tick(0)
        with rec.span("dispatch"):
            pass
        rec.end_tick()
        rec.chunk_spans(1, 0, ("wire.decode", rec.now(), rec.now()))
        (tick,) = rec.ticks()
        (_, s0, s1), = tick["spans"]
        start_ns = tick["anchor_ns"] + int(s0 * 1e9) - 1_000_000
        spans = rec.spans_on_profile(start_ns, 0.0, 10.0)
        assert [s[0] for s in spans] == ["tick.dispatch", "wire.decode"]
        name, p0, p1, ids = spans[0]
        assert p0 == pytest.approx(1e-3, abs=1e-6)
        assert p1 - p0 == pytest.approx(s1 - s0, abs=1e-9)
        assert ids == {"tick": 0}
        assert spans[1][3] == {"stream": 1, "seq": 0, "tick": None}
        # a window that ends before the spans selects none
        assert rec.spans_on_profile(start_ns, 0.0, 0.5e-3) == []

    def test_self_seconds_credit_the_child_inside_its_parent(self):
        tick = {"tick": 3}
        spans = [
            ("tick.dispatch", 0.0, 1.0, tick),
            ("tick.stack", 0.1, 0.9, tick),  # child: outweighs its parent
            ("wire.decode", 0.2, 0.6, {"stream": 0, "seq": 4, "tick": None}),
        ]
        got = self_seconds(spans, 0.0, 1.0)
        assert got["tick.dispatch"] == pytest.approx(0.2)
        assert got["tick.stack"] == pytest.approx(0.8)
        assert got["wire.decode"] == pytest.approx(0.4)  # another thread
        inside = self_seconds(spans, 0.5, 2.0)
        assert inside["tick.dispatch"] == pytest.approx(0.1)
        assert inside["tick.stack"] == pytest.approx(0.4)

    def test_shared_clock_with_the_profiler(self, tmp_path):
        """A recorder span and a ``TraceAnnotation`` inside it land
        within 1 ms of each other on the profile's clock."""
        from jax.profiler import ProfileData, TraceAnnotation

        rec = FlightRecorder(capacity=4)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            rec.begin_tick(0)
            with rec.span("dispatch"):
                with TraceAnnotation("repro.obs.clock_probe"):
                    sum(range(200_000))
            rec.end_tick()
        finally:
            jax.profiler.stop_trace()
        (path,) = [
            os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")
        ]
        prof = ProfileData.from_file(path)
        start_ns = None
        probe = []
        for plane in prof.planes:
            if plane.name == "Task Environment":
                start_ns = int(dict(plane.stats)["profile_start_time"])
            for line in plane.lines:
                probe += [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name == "repro.obs.clock_probe"
                ]
        assert start_ns is not None and len(probe) == 1
        (a0, a1), = probe
        (name, s0, s1, _), = rec.spans_on_profile(start_ns, 0.0, 1e9)
        assert name == "tick.dispatch"
        assert abs(s0 - a0) < 1e-3 and abs(s1 - a1) < 1e-3


def _wire_run(recorder):
    """Two streams, two data frames each, over loopback; two ingest
    ticks.  Returns the served states and the sent keys."""
    srv = _server(capacity=2, queue_depth=2)
    srv.recorder = recorder
    ingest = IngestServer(srv)
    loop = Loopback(ingest)
    chunks = _sensor_chunks(2, n_frames=16)
    sent = []
    for sid in (3, 5):
        assert loop.send(codec.encode_control(codec.OP_OPEN, sid)).ok
    for seq, c in enumerate(chunks):
        for sid in (3, 5):
            assert loop.send(codec.encode_chunk(
                c, stream_id=sid, seq=10 + seq, timestamp_ns=seq
            )).ok
            sent.append((sid, 10 + seq))
    ingest.tick()
    ingest.tick()
    states = {sid: jax.device_get(srv.export(sid)) for sid in (3, 5)}
    return srv, states, sent


class TestWireTracing:
    def test_ingest_tick_leaves_chunk_and_tick_spans(self):
        rec = FlightRecorder(capacity=16)
        srv, _, sent = _wire_run(rec)
        chunks = rec.chunks()
        assert [(c["stream"], c["seq"]) for c in chunks] == sent
        for c in chunks:
            # the decode runs before the ingest lock is taken
            assert [n for n, _, _ in c["spans"]] == [
                "wire.decode", "wire.lock_wait", "queue.wait",
            ]
            (_, d0, d1), (_, w0, w1), (_, q0, q1) = c["spans"]
            assert d0 <= d1 <= w0 <= w1 <= q0 <= q1
        ticks = rec.ticks()
        assert [c["tick"] for c in chunks] == [0, 0, 1, 1]
        for tk in ticks:
            names = [n for n, _, _ in tk["spans"]]
            assert names[0] == "lock_wait" and names.count("stack") == 1
            spans = {n: (a, b) for n, a, b in tk["spans"]}
            (l0, l1), (t0, _) = spans["lock_wait"], spans["ingest"]
            assert l0 <= l1 <= t0
            (a, b), (c0, c1) = spans["dispatch"], spans["stack"]
            assert a <= c0 <= c1 <= b  # stack nests inside dispatch
            assert [(c["stream"], c["seq"]) for c in tk["chunks"]] == (
                sent[:2] if tk["tick"] == 0 else sent[2:]
            )

    def test_detached_recorder_records_nothing_and_serves_the_same(self):
        rec = FlightRecorder(capacity=16)
        _, traced, _ = _wire_run(rec)
        n_spans = rec.n_spans
        srv, plain, _ = _wire_run(None)
        assert srv.recorder is None
        # nothing of the detached run reached the recorder
        assert (rec.n_spans, rec.n_chunks_recorded) == (n_spans, 4)
        assert len(rec.ticks()) == 2
        for sid in traced:
            for a, b in zip(jax.tree.leaves(traced[sid]),
                            jax.tree.leaves(plain[sid])):
                assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# StreamServer integration: phase spans, events, registry == views
# ---------------------------------------------------------------------------


class TestServerTracing:
    def test_tick_leaves_phase_spans_and_events(self):
        srv = _server()
        srv.recorder = FlightRecorder(capacity=8)
        srv.admit("a")
        chunks = _sensor_chunks(0, n_frames=24)
        for c in chunks:
            srv.submit("a", c)
            srv.tick()
        ticks = srv.recorder.ticks()
        assert len(ticks) == len(chunks)
        span_names = {s[0] for t in ticks for s in t["spans"]}
        assert span_names == {
            "ingest", "schedule", "dispatch", "stack", "readback",
        }
        events = [e[0] for t in ticks for e in t["events"]]
        assert events.count("admit") == 0  # admit happened pre-tick 0
        srv.close("a")
        srv.recorder.begin_tick(srv.n_ticks)
        srv.admit("b")
        srv.close("b")
        srv.recorder.end_tick()
        last = srv.recorder.ticks()[-1]
        assert [e[0] for e in last["events"]] == ["admit", "evict"]

    def test_registry_backs_server_counters_bit_identically(self):
        srv = _server()
        srv.admit("a")
        for c in _sensor_chunks(0, n_frames=16):
            srv.submit("a", c)
            srv.tick()
        sc = srv.server_counters()
        reg = srv.metrics
        assert sc["n_ticks"] == reg.value("serve_ticks_total")
        assert sc["n_admitted"] == reg.value("serve_admitted_total")
        assert sc["n_evicted"] == reg.value("serve_evicted_total")
        assert sc["n_dispatches"] == reg.value("serve_dispatches_total")
        assert sc["frames_served"] == reg.value("serve_frames_served_total")
        assert sc["n_live"] == reg.value("serve_live_streams")
        assert sc["degrade_level"] == reg.value("serve_degrade_level")
        # and the export path carries the same numbers
        assert f"serve_ticks_total {sc['n_ticks']}" in reg.to_prometheus()


# ---------------------------------------------------------------------------
# Three-view consistency after a mixed loss/overload soak
# ---------------------------------------------------------------------------


class TestCounterConsistency:
    def _soak(self):
        """A deliberately hostile little run: overload (queue_depth 1,
        double submits), unknown-stream sends, an out-of-order replay,
        and a seq gap — every NACK family and gap counter fires."""
        srv = _server(capacity=2, queue_depth=1)
        srv.degrade = DegradeController(
            DegradeConfig(), metrics=srv.metrics
        )
        ingest = IngestServer(srv)
        loop = Loopback(ingest)
        chunks = _sensor_chunks(1, n_frames=64)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 1)).ok
        seq = 0
        for t in range(4):
            for c in (chunks[2 * t], chunks[2 * t + 1]):
                loop.send(codec.encode_chunk(
                    c, stream_id=1, seq=seq, timestamp_ns=seq
                ))  # second submit of each tick hits backpressure
                seq += 1
            # loss-shaped traffic: an unknown stream, a stale replay
            loop.send(codec.encode_chunk(
                chunks[0], stream_id=99, seq=0, timestamp_ns=0
            ))
            loop.send(codec.encode_chunk(
                chunks[0], stream_id=1, seq=0, timestamp_ns=0
            ))
            ingest.tick()
        # a dropped frame: jump the cursor → counted seq gap
        loop.send(codec.encode_chunk(
            chunks[0], stream_id=1, seq=seq + 3, timestamp_ns=0
        ))
        ingest.tick()
        return srv, ingest

    def test_all_three_views_read_the_same_cells(self):
        srv, ingest = self._soak()
        reg = srv.metrics
        assert ingest.metrics is reg  # one registry end to end

        wc = ingest.counters()
        assert wc["nacks"] != {} and wc["n_seq_gaps"] > 0
        for key, metric in (
            ("n_messages", "wire_messages_total"),
            ("n_frames_in", "wire_frames_in_total"),
            ("n_opened", "wire_opened_total"),
            ("n_closed", "wire_closed_total"),
            ("n_resumed", "wire_resumed_total"),
            ("n_dup_suppressed", "wire_dup_suppressed_total"),
            ("n_credit_requests", "wire_credit_requests_total"),
            ("n_credit_granted", "wire_credit_granted_total"),
            ("credit_outstanding", "wire_credit_outstanding"),
        ):
            assert wc[key] == reg.value(metric), key
        assert wc["nacks"] == {
            dict(lk)["status"]: m.value
            for lk, m in reg.family("wire_nacks_total").items()
        }
        assert wc["seq_gaps_by_stream"] == {
            dict(lk)["stream"]: m.value
            for lk, m in reg.family("wire_seq_gaps_total").items()
        }

        sc = srv.server_counters()
        assert sc["n_backpressure"] > 0
        for key, metric in (
            ("n_ticks", "serve_ticks_total"),
            ("n_admitted", "serve_admitted_total"),
            ("n_backpressure", "serve_backpressure_total"),
            ("n_dispatches", "serve_dispatches_total"),
            ("frames_served", "serve_frames_served_total"),
            ("n_live", "serve_live_streams"),
            ("n_shed_stale", "serve_shed_stale_total"),
            ("degrade_level", "serve_degrade_level"),
        ):
            assert sc[key] == reg.value(metric), key
        # the degrade controller shares the registry too
        assert srv.degrade.counters()["n_observed"] == reg.value(
            "degrade_observed_total"
        )
        # and one snapshot carries all three families
        snap = reg.snapshot()
        for name in ("serve_ticks_total", "wire_messages_total",
                     "degrade_observed_total"):
            assert name in snap


# ---------------------------------------------------------------------------
# STATUS: loopback + TCP both return the host-side truth
# ---------------------------------------------------------------------------


class TestStatus:
    def _loaded_ingest(self):
        srv = _server()
        srv.degrade = DegradeController(
            DegradeConfig(), metrics=srv.metrics
        )
        ingest = IngestServer(srv)
        loop = Loopback(ingest)
        assert loop.send(codec.encode_control(codec.OP_OPEN, 5)).ok
        for seq, c in enumerate(_sensor_chunks(2, n_frames=16)):
            assert loop.send(codec.encode_chunk(
                c, stream_id=5, seq=seq, timestamp_ns=seq
            )).ok
            ingest.tick()
        return ingest, loop

    def test_loopback_status_equals_collect_status(self):
        ingest, loop = self._loaded_ingest()
        got = loop.status()
        with ingest.lock:
            want = json.loads(json.dumps(collect_status(ingest)))
        assert got == want
        assert got["schema"] == 1
        assert got["tick"] == ingest.srv.n_ticks > 0
        assert got["tiers"][0]["n_active"] == 1
        assert got["seq_cursors"] == {"5": 1}
        assert got["degrade"]["attached"] is True
        assert got["wire_counters"]["n_frames_in"] == 2
        # every NACK code a client can receive is in the reply
        assert set(got["status_reasons"]) == {
            str(c) for c in codec.STATUS_REASONS
        }

    def test_status_roundtrips_the_codec(self):
        ingest, loop = self._loaded_ingest()
        raw = loop.roundtrip(codec.encode_control(codec.OP_STATUS, 0))
        kind, payload = codec.decode_message(raw)
        assert kind == "status"
        again = loop.status()
        # each STATUS request is itself a counted wire message, so the
        # second snapshot drifts by exactly one n_messages
        assert again["wire_counters"].pop("n_messages") == (
            payload["wire_counters"].pop("n_messages") + 1
        )
        assert payload == again

    def test_status_over_tcp(self):
        ingest, _ = self._loaded_ingest()
        try:
            host, port = ingest.start_tcp_in_thread()
        except (OSError, PermissionError) as e:  # pragma: no cover
            pytest.skip(f"cannot bind local TCP socket: {e}")
        try:
            with WireClient(host, port) as client:
                got = client.status()
            with ingest.lock:
                want = json.loads(json.dumps(collect_status(ingest)))
            assert got == want
        finally:
            ingest.stop()


# ---------------------------------------------------------------------------
# k_trajectory_limit: bounded rung history, unchanged decisions
# ---------------------------------------------------------------------------


class TestKTrajectoryLimit:
    def test_controller_ring_keeps_the_most_recent(self):
        # overflow climbs; peak_full=100 never satisfies the shrink
        # margin, so the rung saturates at the top and stays
        ctl = KLadderController((4, 8, 16), history_limit=3)
        for _ in range(7):
            ctl.begin_chunk()
            ctl.update(overflow=1, peak_full=100)
        assert list(ctl.k_trajectory) == [16, 16, 16]
        unbounded = KLadderController((4, 8, 16))
        for _ in range(7):
            unbounded.begin_chunk()
            unbounded.update(overflow=1, peak_full=100)
        assert list(unbounded.k_trajectory) == [4, 8] + [16] * 5
        assert list(unbounded.k_trajectory)[-3:] == list(ctl.k_trajectory)

    def test_history_limit_validation(self):
        with pytest.raises(ValueError, match="history_limit"):
            KLadderController((4, 8), history_limit=0)
        with pytest.raises(ValueError, match="k_trajectory_limit"):
            StreamServer(
                api.EPICCompressor(_ecfg()),
                ServerConfig(k_trajectory_limit=0),
            )

    def test_decisions_identical_with_and_without_the_bound(self):
        runs = []
        for limit in (None, 2):
            ctl = KLadderController((4, 8, 16), history_limit=limit)
            ks = []
            for i in range(12):
                ks.append(ctl.begin_chunk())
                ctl.update(
                    overflow=1 if i % 3 == 0 else 0,
                    peak_full=1 if i % 3 == 2 else 100,
                )
            runs.append(ks)
        assert runs[0] == runs[1]

    def test_server_config_bounds_per_stream_history(self):
        srv = StreamServer(
            api.EPICCompressor(_ecfg(prefilter_k=4)),
            ServerConfig(
                capacity=1, chunk_frames=CHUNK, queue_depth=2,
                k_ladder=(4, 8), k_trajectory_limit=2,
            ),
        )
        srv.admit("a")
        for c in _sensor_chunks(0, n_frames=32):
            srv.submit("a", c)
            srv.tick()
        traj = srv.telemetry("a").as_dict()["k_trajectory"]
        assert len(traj) == 2  # 4 chunks served, ring kept the last 2


# ---------------------------------------------------------------------------
# FailureInjector: kill points leave flight-dump post-mortems
# ---------------------------------------------------------------------------


class TestFaultDumps:
    def test_kill_point_dumps_before_raising(self, tmp_path):
        rec = FlightRecorder(capacity=4, clock=_FakeClock())
        rec.begin_tick(0)
        rec.event("nack", status="backpressure")
        inj = FailureInjector(
            [("mid_tick", 3)], recorder=rec, dump_dir=str(tmp_path)
        )
        inj.maybe_fail("benign")  # not a kill point
        with pytest.raises(WorkerFailure):
            inj.maybe_fail(("mid_tick", 3))
        (path,) = inj.dump_paths
        assert os.path.basename(path) == "flight-mid_tick---3-0.json"
        with open(path) as f:
            doc = json.load(f)
        assert any(
            e["name"] == "nack" for e in doc["traceEvents"]
        )
        # each point fires once: the replay of the same point survives
        inj.maybe_fail(("mid_tick", 3))

    def test_without_a_recorder_nothing_is_written(self, tmp_path):
        inj = FailureInjector(["x"], dump_dir=str(tmp_path))
        with pytest.raises(WorkerFailure):
            inj.maybe_fail("x")
        assert inj.dump_paths == [] and os.listdir(str(tmp_path)) == []

    def test_dump_failure_never_masks_the_fault(self, tmp_path):
        rec = FlightRecorder(capacity=2, clock=_FakeClock())
        inj = FailureInjector(
            ["x"], recorder=rec,
            dump_dir=str(tmp_path / "missing" / "dir"),
        )
        with pytest.raises(WorkerFailure):
            inj.maybe_fail("x")
        assert inj.dump_paths == []


# ---------------------------------------------------------------------------
# LoadGen RTT: wall-clock percentiles with deterministic sample counts
# ---------------------------------------------------------------------------


class TestLoadGenRTT:
    def test_rtt_counts_every_send(self):
        srv = _server(capacity=4, queue_depth=2)
        gen = LoadGen(
            LoadConfig(seed=3, ticks=6, arrival_rate=1.0),
            _sensor_chunks(0, n_frames=16), IngestServer(srv),
        )
        s = gen.run()
        rtt = s["rtt"]
        sends = (
            s["n_admitted"] + s["n_rejected"]  # OPENs
            + s["n_frames_sent"] + s["n_closed"]
        )
        assert rtt["count"] == sends > 0
        assert rtt["p50_ms"] is not None and rtt["max_ms"] > 0
