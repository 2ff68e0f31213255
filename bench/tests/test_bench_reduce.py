"""The reductions from raw samples and traces to the benchmark's numbers,
on hand-made inputs."""

import math

import pytest

from bench import kernels, stats, tracing
from bench.harness import LayerInput, Stepped, reader

TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%reproject_match_fused.8 = (f32[5,48,8]) call()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_masked" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.clock" } }
}
planes {
  id: 3
  name: "Task Environment"
  stats { metadata_id: 1 uint64_value: 5000000000 }
  stats { metadata_id: 2 uint64_value: 5010000000 }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
  stat_metadata { key: 2 value { id: 2 name: "profile_stop_time" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return tracing.from_profile(ProfileData.from_text_proto(TRACE))


def test_percentile_is_exact_nearest_rank():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == 5.0
    assert stats.percentile(xs, 20) == 1.0
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([1.0, math.inf], 95) == math.inf


def test_rate_snaps_to_readbacks():
    ticks = [(0.5, 10), (1.0, 20), (2.0, 30), (3.0, 40), (3.5, 50)]
    rate, units, secs = stats.snapped_rate(ticks, 0.9, 3.2)
    assert (units, secs) == (140, 3.0)
    assert rate == pytest.approx(140 / 3.0)
    assert stats.snapped_rate(ticks, 1.1, 1.9) == (30.0, 30, 1.0)
    assert stats.snapped_rate(ticks, 0.0, 0.7) == (40.0, 20, 0.5)
    assert stats.snapped_rate([(1.0, 5)], 0.0, 2.0) is None


def test_rate_keeps_a_stall_at_the_window_edge():
    ticks = [(0.0, 10), (1.0, 10), (2.0, 10), (10.0, 10)]
    rate, units, secs = stats.snapped_rate(ticks, 0.5, 3.0)
    assert (units, secs) == (30, 10.0)


def test_trace_busy_kernel_and_gaps(trace):
    assert trace.window_s == pytest.approx(0.01)
    assert list(trace.device) == ["/device:TPU:0"]
    # Ops cover 1-4 ms and 6-7 ms: the modules line is not read.
    assert tracing.busy_seconds(trace) == pytest.approx(0.004)
    assert tracing.kernel_seconds(trace, kernels.REPROJECT_MATCH) == pytest.approx(0.003)
    assert tracing.top_ops(trace)[0] == ["%reproject_match_fused.8", pytest.approx(0.003)]
    spans = [
        ("tick.ingest", 0.0040, 0.0041), ("tick.dispatch", 0.0041, 0.0058),
        ("wire.send", 0.0070, 0.0100), ("wire.send", 0.0040, 0.0060),
    ]
    gaps = tracing.idle_gaps(trace, spans)
    assert gaps[0] == ["wire.send", pytest.approx(0.003)]
    assert gaps[1] == ["tick.dispatch", pytest.approx(0.002)]
    assert tracing.idle_gaps(trace, [])[0] == ["host_other", pytest.approx(0.003)]
    assert trace.start_ns == 5000000000
    # A host whose wall clock reads 7 s at monotonic 0 places monotonic
    # 0 at 2 s into a profile that started at wall 5 s.
    assert tracing.host_offset(trace, 7_000_000_000) == pytest.approx(2.0)


def test_readers(trace):
    cfg = {"chunk_frames": 10, "patch": 16, "window": 32}
    x = LayerInput(
        trace=trace,
        ticks=[
            {"spans": [("dispatch", 0.0, 0.002), ("readback", 0.002, 0.003)]},
            {"spans": [("ingest", 0.0, 0.001)]},
            {"spans": [("dispatch", 0.0, 0.004), ("readback", 0.004, 0.005)]},
        ],
        decode_s=[0.001, 0.003],
        stepped=[Stepped(0.0, 0, 8, 10), Stepped(0.0, 1, 16, 5)],
        cfg=cfg,
        peaks={"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
    )
    assert reader("wire.decode_ms.backfill")(x) == pytest.approx(2.0)
    assert reader("tick.dispatch_ms.live")(x) == pytest.approx(3.0)
    assert reader("tick.readback_ms.backfill")(x) == pytest.approx(1.0)
    assert reader("step.device_us_per_frame.backfill")(x) == pytest.approx(200.0)
    assert reader("device.idle_pct.backfill")(x) == pytest.approx(60.0)
    ops, nbytes = kernels.reproject_match_work(cfg, 8 * 10 + 16 * 5)
    least = max(ops / 1.97e14, nbytes / 8.19e11)
    assert nbytes / 8.19e11 > ops / 1.97e14
    assert reader("trd_roofline.backfill")(x) == pytest.approx(100 * least / 0.003)
    empty = x._replace(decode_s=[], ticks=[], stepped=[])
    for name in ("wire.decode_ms", "tick.dispatch_ms", "trd_roofline", "step.device_us_per_frame"):
        assert reader(name + ".backfill")(empty) is None
