"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The script itself needs a TPU; here its phase functions run at 64x64
frames, a 16-entry DC buffer and 2 streams (4 on the virtual mesh), so
the serving, checkpoint/restore and comparison logic are pinned without
a chip — and ``main()`` is shown to refuse a CPU-only process.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY = cs.Size(
    frame=64, capacity=16, streams=2, chunk_frames=2, chunks=3, k_ladder=(8, 16)
)

_SUB_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
for _k in ("JAX_PLATFORMS", "HOME"):
    if _k in os.environ:
        _SUB_ENV[_k] = os.environ[_k]


@pytest.fixture(scope="module")
def streams():
    return cs.make_streams(0, TINY)


@pytest.fixture(scope="module")
def cfg():
    return cs.epic_config(TINY)


@pytest.fixture(scope="module")
def scfg():
    return cs.server_config(TINY)


@pytest.fixture(scope="module")
def ref(cfg, streams):
    return cs.reference(cfg, TINY, streams)


@pytest.fixture(scope="module")
def ref_run(cfg, scfg, streams, tmp_path_factory):
    return cs.serve(
        cs.EPICCompressor(cfg), scfg, streams,
        checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")),
    )


def test_make_streams_shapes(streams):
    assert len(streams) == 2
    s = streams[0]
    assert s.frames.shape == (TINY.frames, TINY.frame, TINY.frame, 3)
    assert s.depth.shape == (TINY.frames, TINY.frame, TINY.frame)
    assert not np.array_equal(streams[0].frames, streams[1].frames)


def test_served_streams_equal_reference(ref_run, ref):
    lines, ok = cs.compare(ref_run.results, ref, "serve")
    assert ok and len(lines) == 2
    assert all("bitwise equal" in x for x in lines)
    assert ref_run.frames_served == TINY.streams * TINY.frames
    assert ref_run.retraces == 0
    assert ref_run.n_devices == 1


def test_restore_equals_uninterrupted(ref_run):
    assert ref_run.ckpt_bytes > 0
    assert ref_run.n_devices_restored == 1
    assert cs.compare(ref_run.restored, ref_run.results, "restored")[1]


def test_fused_backend_equals_reference(cfg, scfg, streams, ref):
    run = cs.serve(
        cs.EPICCompressor(cfg._replace(backend="fused")), scfg, streams
    )
    assert cs.compare(run.results, ref, "fused")[1]
    assert run.retraces == 0


def test_compare_reports_a_mismatch(ref):
    bad = [
        cs.StreamResult(
            {**r.export, "popularity": r.export["popularity"] + 1.0},
            r.counters,
        )
        for r in ref
    ]
    lines, ok = cs.compare(bad, ref, "mutated")
    assert not ok and all("MISMATCH" in x and "popularity" in x for x in lines)
    short = [cs.StreamResult(ref[0].export, {**ref[0].counters,
                                             "n_inserted": -1})]
    lines, ok = cs.compare(short, ref[:1], "mutated")
    assert not ok and "n_inserted" in lines[0]
    assert not cs.compare(ref[:1], ref, "mutated")[1]


def test_verdict_reports_every_failure():
    """A failed check does not stop the run; closing it raises with all."""
    v = cs.Verdict()
    v.expect(True, "holds")
    v.expect(False, "first")
    v.expect(False, "second")
    with pytest.raises(cs.SmokeError, match="first; second"):
        v.close()
    cs.Verdict().close()


@pytest.mark.parametrize("backend", cs.PALLAS_BACKENDS)
def test_cpu_step_carries_no_kernel(backend, cfg, streams):
    """Lowered for the CPU, a Pallas backend runs the interpreter."""
    assert not cs.has_kernel(cfg._replace(backend=backend), TINY, streams[0])


def test_learned_cnns_compare(streams):
    """The learned CNNs give finite logits on the HIR grid and full-size
    depth; a perturbation past the tolerance is reported per stream."""
    params = cs.learned_params(0)
    out = cs.learned_outputs(params, streams, cs.jax.devices("cpu")[0])
    logits, dmap = out[0]
    assert logits.shape == (TINY.frames, cs.HIR_GRID, cs.HIR_GRID)
    assert dmap.shape == (TINY.frames, TINY.frame, TINY.frame)
    lines, ok = cs.compare_learned(out, out, "same")
    assert ok and all("within tolerance" in x for x in lines)
    bumped = [(lg + 2 * cs.LOGIT_ATOL, d) for lg, d in out]
    lines, ok = cs.compare_learned(bumped, out, "bumped")
    assert not ok and all("MISMATCH" in x for x in lines)
    deeper = [(lg, d * (1 + 2 * cs.DEPTH_RTOL)) for lg, d in out]
    assert not cs.compare_learned(deeper, out, "deeper")[1]


def test_one_chip_phases_on_the_cpu(capsys):
    """The whole one-chip run at a tiny size: on the CPU every check
    holds except the Mosaic kernels, which only a TPU lowering carries."""
    with pytest.raises(cs.SmokeError) as e:
        cs.one_chip(0, TINY)
    assert str(e.value) == "failed: " + "; ".join(
        f"the {b} step is lowered to a Mosaic kernel" for b in cs.PALLAS_BACKENDS
    )
    out = capsys.readouterr().out
    for what in (
        "serve[ref] vs cpu reference",
        "restored vs uninterrupted",
        *(f"serve[{b}] vs cpu reference" for b in cs.PALLAS_BACKENDS),
    ):
        for sid in range(TINY.streams):
            assert f"{what} stream {sid}: bitwise equal" in out
    for sid in range(TINY.streams):
        assert f"learned CNNs vs cpu stream {sid}: within tolerance" in out
    assert "retraces after warmup 0" in out


def test_main_refuses_a_cpu_only_process(capsys):
    assert cs.main([]) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(last)
    assert out["ok"] is False and "not a TPU" in out["error"]


def test_mesh_serving_equals_one_device():
    """The ``--chips 4`` run on four virtual CPU devices: slot state
    sharded over all four, before and after a restore onto the mesh, and
    every stream equal to one device's."""
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        cs.four_chips(1, cs.{TINY._replace(streams=4)!r})
        print("MESH_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env=_SUB_ENV, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "MESH_OK" in r.stdout
    assert "mesh4 vs one chip stream 3: bitwise equal" in r.stdout
    assert "restored slot state on 4 device(s)" in r.stdout
