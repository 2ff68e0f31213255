"""A configuration's ``"path"`` chooses the served path: a path added as
files is dispatched by name, a configuration without the key runs the
compressor's, and an unknown name fails before any device work."""

import pytest

from bench import harness
from bench.paths import compress
from bench.tests import _toy
from bench.tests.paths import status
from repro.serve import StreamServer
from repro.wire import codec


@pytest.fixture
def with_test_paths(monkeypatch):
    monkeypatch.setattr(harness, "PATHS", harness.PATHS + ("bench.tests.paths",))


def test_path_is_dispatched_by_name(tmp_path, with_test_paths):
    out = _toy.run(harness, tmp_path, path="status")
    assert out.correct, out.checks
    assert out.checks == {"status_unanswered": {"value": 0, "limit": 0}}
    p50 = out.metrics["latency_p50_ms"]["value"]
    assert 0 < p50 < float("inf")
    assert out.latencies and all(x > 0 for _, x in out.latencies)
    assert out.metrics["frames_per_s"]["value"] > 0
    assert out.metrics["setup_s"]["value"] > 0


def test_no_path_runs_the_compressor(tmp_path, monkeypatch):
    banks, servers = [], []
    make_bank = compress.make_bank
    monkeypatch.setattr(compress, "make_bank", lambda *a: banks.append(make_bank(*a)) or banks[-1])
    default = _toy.run(harness, tmp_path, before_window=servers.append)
    named = _toy.run(harness, tmp_path, before_window=servers.append, path="compress")
    assert all(isinstance(s, StreamServer) for s in servers) and len(servers) == 2
    head = codec.FRAME_HEADER.size  # re-stamped at each send
    payloads = [[bytes(f[head:]) for f in b.frames] for b in banks]
    assert payloads[0] == payloads[1]  # the same seed, the same inputs
    exact = {k: {"value": 0, "limit": lim} for k, lim in compress.LIMITS.items()}
    assert default.checks == named.checks == exact
    assert default.correct and named.correct
    assert default.attempted > 0 and named.attempted > 0


def test_unknown_path_fails_before_device_work(tmp_path, monkeypatch, with_test_paths):
    def no_bank(*a, **kw):
        raise AssertionError("the bank was rendered")

    monkeypatch.setattr(compress, "make_bank", no_bank)
    with pytest.raises(KeyError) as e:
        _toy.run(harness, tmp_path, path="no_such_path")
    assert "'compress', 'status'" in str(e.value)
    assert harness.path_names() == ["compress", "status"]
    assert harness.path_module("status") is status
