"""Every file the benchmark names parses, resolves by name, and keeps to
the shape ``BENCHMARK.json`` promises."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = harness.spec()


def test_spec_keys_and_names():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names), names
    assert len({x["name"] for x in SPEC["configs"]}) == len(SPEC["configs"])
    assert len({x["name"] for x in SPEC["workloads"]}) == len(SPEC["workloads"])
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in metric_names


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    w = harness.workload(SPEC, cell)
    cfg = harness.config(SPEC, w["config"])
    mix = harness.traffic(w["traffic"])
    assert w["chips"] == 1
    assert mix["loop"] in ("closed", "open")
    assert cfg["frame"] % cfg["patch"] == 0
    assert mix["clip_frames"] % cfg["chunk_frames"] == 0
    assert harness.bank_clips(cfg, mix) >= 1
    e2e, layer = harness.metrics_of(SPEC, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        assert m["moves"] in names  # every per-layer metric moves one it reports
        assert callable(harness.reader(m["name"]))


def test_configs_name_their_files():
    for c in SPEC["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg


def test_peaks_name_the_chip():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        harness.peaks("no such chip")


def test_command_refuses_without_a_tpu():
    cell = SPEC["workloads"][0]["name"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, *SPEC["command"][1:]),
         "--workload", cell, "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
