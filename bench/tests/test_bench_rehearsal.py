"""The harness end to end on the CPU at a toy size: a sound run is
correct, and a run with the served path broken underneath is not."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness, reference
from bench.tests import _toy


def test_sound_run_is_correct(tmp_path):
    out = _toy.run(harness, tmp_path)
    assert out.correct, out.checks
    assert set(out.checks) == set(harness.LIMITS)
    assert out.checks["unserved_chunks"] == {"value": 0, "limit": 0}
    assert out.checks["counters_off"] == {"value": 0, "limit": 0}
    assert out.metrics["frames_per_s"]["value"] > 0
    assert out.metrics["setup_s"]["value"] > 0
    assert out.device["platform"] == "cpu"
    assert out.attempted > 0 and out.failed == 0


def test_open_loop_reports_exact_latency(tmp_path):
    out = _toy.run(harness, tmp_path, loop="open", seconds=1.0)
    assert out.correct, out.checks
    p50 = out.metrics["latency_p50_ms"]["value"]
    p95 = out.metrics["latency_p95_ms"]["value"]
    assert 0 < p50 <= p95 < float("inf")


def _state_unchanged(srv):
    step = srv.pool.step

    def frozen(chunks, **kw):
        before = jax.tree.map(jnp.copy, srv.pool.states)
        stats = step(chunks, **kw)
        srv.pool.states = before
        return stats

    srv.pool.step = frozen


def _half_batch(srv):
    step = srv.pool.step
    half = jnp.arange(srv.pool.capacity) < srv.pool.capacity // 2

    def halved(chunks, mask=None, **kw):
        return step(chunks, mask=mask & half, **kw)

    srv.pool.step = halved


def _answer_altered(srv):
    step = srv.pool.step

    def altered(chunks, **kw):
        stats = step(chunks, **kw)
        st = srv.pool.states
        buf = st.sessions.buf
        srv.pool.states = st._replace(
            sessions=st.sessions._replace(
                buf=buf._replace(rgb=buf.rgb + 1.0 / 255.0)
            )
        )
        return stats

    srv.pool.step = altered


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_batch, _answer_altered],
    ids=["state_unchanged", "half_batch", "answer_altered"],
)
def test_broken_path_is_not_correct(tmp_path, fault):
    out = _toy.run(harness, tmp_path, before_window=fault)
    assert not out.correct
    assert any(c["value"] > c["limit"] for c in out.checks.values())


def test_control_lower_precision_fails(tmp_path):
    """The reference in bfloat16, put in the program's place, differs."""
    cfg, mix = _toy.config(), _toy.traffic()
    bank = harness.make_bank(11, cfg, mix)
    chunks = list(harness.stream_chunks(bank, 0, list(range(6)), mix["streams"]))
    p = reference.Params.of(cfg)
    want = reference.run_stream(p, cfg["k_ladder"], chunks)
    got = reference.run_stream(p, cfg["k_ladder"], chunks, dtype=jnp.bfloat16)
    d = reference.compare(got, want)
    share = 100.0 * d.entries / want.retained["valid"].shape[0]
    assert share > harness.LIMITS["entries_off_pct"]
    assert reference.compare(want, want) == reference.Diff(0, 0, 0, [])


def test_counters_are_held_exactly():
    """A served session whose counters alone are wrong differs."""
    cfg, mix = _toy.config(), _toy.traffic()
    bank = harness.make_bank(12, cfg, mix)
    chunks = list(harness.stream_chunks(bank, 1, list(range(4)), mix["streams"]))
    want = reference.run_stream(reference.Params.of(cfg), cfg["k_ladder"], chunks)
    k = list(want.counters["k_trajectory"])
    bad = [
        dict(want.counters, n_frames=want.counters["n_frames"] + 1),
        dict(want.counters, k_trajectory=k[:-1] + [k[-1] * 2]),
        dict(want.counters, n_inserted=want.counters["n_inserted"] + 3),
    ]
    diffs = [reference.compare(reference.Result(want.retained, c), want) for c in bad]
    assert [(d.entries, d.counters, d.inserted_gap) for d in diffs] == [(0, 1, 0), (0, 1, 0), (0, 0, 3)]
