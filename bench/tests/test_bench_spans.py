"""The readers of the program's own spans, and the reduction of device
time under named scopes, on hand-made inputs: each reads what the
program records, and gives no value where a program records none of
it."""

import pytest

from bench import scopes, tracing
from bench.harness import LayerInput, reader
from bench.paths.compress import Stepped

# Ops at 1-3 ms (under ``tsrc``), 2-4 ms (under ``tsrc``, overlapping)
# and 6-7 ms (under ``bypass``); one op at 8-9 ms carries no path.
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000
             stats { metadata_id: 1 str_value: "jit(step)/while/body/tsrc/mul" } }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 2000000000
             stats { metadata_id: 1 str_value: "jit(step)/while/body/tsrc/jit(_reproject_match_fused)/pallas_call" } }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 1000000000
             stats { metadata_id: 1 str_value: "jit(step)/while/body/bypass/sub" } }
    events { metadata_id: 3 offset_ps: 8000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%reproject_match_fused.8 = (f32[5,48,8]) call()" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
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


def _chunk(sid, seq, lock, decode, wait):
    return {
        "stream": sid, "seq": seq, "tick": 0,
        "spans": [("wire.lock_wait", 0.0, lock), ("wire.decode", lock, lock + decode),
                  ("queue.wait", 1.0, 1.0 + wait)],
    }


@pytest.fixture
def x(trace):
    return LayerInput(
        trace=trace,
        ticks=[
            {"spans": [("lock_wait", 0.0, 0.001), ("ingest", 0.001, 0.002),
                       ("dispatch", 0.002, 0.006), ("stack", 0.003, 0.005),
                       ("readback", 0.006, 0.007)],
             "chunks": [_chunk(0, 4, 0.002, 0.040, 0.100), _chunk(1, 4, 0.004, 0.050, 0.300)]},
            {"spans": [("lock_wait", 0.0, 0.003), ("ingest", 0.003, 0.004)], "chunks": []},
            {"spans": [("lock_wait", 0.0, 0.005), ("dispatch", 0.005, 0.009),
                       ("stack", 0.005, 0.009)],
             "chunks": [_chunk(0, 5, 0.0, 0.060, 0.200)]},
        ],
        decode_s=[0.001],
        stepped=[Stepped(0.0, 0, 8, 10), Stepped(0.0, 1, 16, 5)],
        cfg={"chunk_frames": 10, "patch": 16, "window": 32},
        peaks={"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
    )


def test_span_readers(x):
    # Ticks with a dispatch span only: lock waits of 1 and 5 ms.
    assert reader("tick.lock_wait_ms.backfill")(x) == pytest.approx(3.0)
    assert reader("tick.stack_ms.live")(x) == pytest.approx(3.0)
    # Per chunk popped: three chunks.
    assert reader("wire.lock_wait_ms.backfill")(x) == pytest.approx(2.0)
    assert reader("wire.decode_span_ms.live")(x) == pytest.approx(50.0)
    assert reader("queue.wait_ms.backfill")(x) == pytest.approx(200.0)


def test_scope_table(trace):
    # tsrc ops cover 1-4 ms (overlapping), bypass 6-7 ms, the rest 8-9 ms.
    assert scopes.seconds_under(trace, "tsrc") == pytest.approx(0.003)
    assert scopes.table(trace, ["tsrc", "bypass"]) == {
        "tsrc": pytest.approx(0.003), "bypass": pytest.approx(0.001),
        "(none)": pytest.approx(0.001),
    }
    # The stage's own name, not a path that merely contains it.
    assert scopes.seconds_under(trace, "tsr") == 0.0
    assert scopes.seconds_under(trace, "_reproject_match_fused") == 0.0


def test_nothing_recorded_reads_none(x):
    bare = x._replace(ticks=[
        {"spans": [(n, a, b) for n, a, b in tk["spans"] if n not in ("lock_wait", "stack")]}
        for tk in x.ticks
    ])
    empty = x._replace(ticks=[], stepped=[])
    names = ("wire.lock_wait_ms", "wire.decode_span_ms", "queue.wait_ms",
             "tick.lock_wait_ms", "tick.stack_ms")
    for name in names:
        assert reader(name + ".backfill")(bare) is None, name
        assert reader(name + ".live")(empty) is None, name
