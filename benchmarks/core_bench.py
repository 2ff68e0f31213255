"""Core streaming throughput: frames/sec + retained bytes per method.

The perf-trajectory benchmark: every registered compressor (EPIC and the
four baselines, plus EPIC on each reproject-match kernel backend and the
sparse-TRD prefilter path) runs the same seeded synthetic stream through
its jitted session ``step``; we record steady-state frames/sec
(post-compile, best-of-``repeats`` walls), the retained-representation
bytes, each row's backend/interpret mode, and its speedup vs the dense
``epic`` row.

``benchmarks/run.py`` writes the summary to the repo-root
``BENCH_core.json`` (the checked-in perf trajectory) and the full
detail to ``benchmarks/results/core_bench.json``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro import api
from repro.core import pipeline as P
from repro.data import synthetic as SYN

RESULTS = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FRAME = 64
PATCH = 16
N_FRAMES = 40
# The paper-default DC-buffer capacity: the dense TRD warps and
# pixel-scores all 192 entries every processed frame, which is exactly
# the hot loop the sparse prefilter (`epic[sparse]`) exists to avoid.
CAPACITY = 192
# Top-K candidate budget of the sparse row (TSRCConfig.prefilter_k).
SPARSE_K = 24
# Patch-axis budget of the sparse row (TSRCConfig.patch_k).  The quick
# grid has (FRAME // PATCH)^2 = 16 patches and the oracle mode marks all
# of them salient, so P_k = M here: tsrc_step statically recognises the
# identity and skips the compaction machinery — this row times the
# entry-sparse path with the patch knob on, NOT the compacted (K, P_k)
# algebra (exercised with P_k < M in tests/test_sparse_v2.py; at this
# tiny M the patch axis is an accounting win, not a CPU-time win).
SPARSE_PATCH_K = 16
BUDGET = 64
# EPIC variants: (row tag, kernel backend, prefilter_k, patch_k).  The
# Pallas backends run in interpret mode on CPU, so only the XLA rows
# (`ref` backend) reflect CPU steady-state speed — the interpret rows
# track correctness-at-speed for accelerator deployment (see each row's
# `interpret` field; `speedup_vs_epic` is relative to the dense `epic`
# row on the same device).  Interpret rows are SKIPPED unless
# ``interpret=True`` (`run.py --interpret`): a 100x-slower interpreted
# kernel row dominates wall time and reads as a bogus "0.1x speedup".
EPIC_VARIANTS = (
    ("epic", "ref", 0, 0),
    ("epic[sparse]", "ref", SPARSE_K, SPARSE_PATCH_K),
    ("epic[pallas]", "pallas", 0, 0),
    ("epic[tiled]", "pallas_tiled", 0, 0),
    ("epic[fused]", "fused", 0, 0),
)
QUICK_TAGS = ("epic", "epic[sparse]", "epic[fused]")
# Backends whose CPU execution is interpret-mode Pallas (not native XLA).
_INTERPRET_BACKENDS = ("pallas", "pallas_tiled", "fused")


def _epic_cfg(
    backend: str, prefilter_k: int = 0, patch_k: int = 0
) -> P.EPICConfig:
    return P.EPICConfig(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=CAPACITY,
        tau=0.10, gamma=0.015, theta=8, window=16, backend=backend,
        prefilter_k=prefilter_k, patch_k=patch_k,
    )


def _make(name: str, backend: str = "ref", prefilter_k: int = 0,
          patch_k: int = 0):
    cls = api.get_compressor(name)
    if name == "epic":
        return cls(_epic_cfg(backend, prefilter_k, patch_k))
    return cls(api.BaselineConfig(
        frame_hw=(FRAME, FRAME), patch=PATCH,
        budget_patches=BUDGET, n_frames=N_FRAMES,
    ))


def _bench_one(comp, chunk, repeats: int) -> Dict:
    step = jax.jit(comp.step)
    state0 = comp.init()
    state, stats = step(state0, chunk)  # compile + first run
    jax.block_until_ready(state)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out, _ = step(state0, chunk)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    retained = int(comp.export(state).memory_bytes())
    return {
        "frames_per_sec": round(chunk.n_frames / best, 2),
        "step_ms": round(best * 1e3, 3),
        "retained_bytes": retained,
    }


def run(quick: bool = False, seed: int = 0, interpret: bool = False) -> Dict:
    t0 = time.time()
    scfg = SYN.StreamConfig(n_frames=N_FRAMES, hw=(FRAME, FRAME), n_obj=5)
    s, _ = SYN.generate_stream(jax.random.PRNGKey(seed), scfg)
    chunk = api.SensorChunk(s.frames, s.poses, s.gazes, s.depth)
    repeats = 2 if quick else 5

    methods: Dict[str, Dict] = {}
    for name in sorted(api.available_compressors()):
        if name == "epic":
            for tag, backend, pk, ppk in EPIC_VARIANTS:
                if quick and tag not in QUICK_TAGS:
                    continue
                is_interp = backend in _INTERPRET_BACKENDS
                if is_interp and not interpret:
                    # An interpret-mode Pallas row is a correctness
                    # vehicle, not a CPU speed number: timing it anyway
                    # burns ~x100 wall time and pollutes the trajectory
                    # with "0.1x" rows.  Mark it skipped so the JSON
                    # stays self-describing.
                    methods[tag] = {
                        "skipped": True,
                        "reason": "interpret-mode pallas; "
                                  "rerun with --interpret to time it",
                        "backend": backend,
                        "interpret": True,
                    }
                    print(f"[core] {tag:13s}   skipped (interpret)")
                    continue
                methods[tag] = _bench_one(
                    _make(name, backend, pk, ppk), chunk, repeats
                )
                methods[tag]["backend"] = backend
                methods[tag]["interpret"] = is_interp
                if pk:
                    methods[tag]["prefilter_k"] = pk
                if ppk:
                    methods[tag]["patch_k"] = ppk
                print(f"[core] {tag:13s} "
                      f"{methods[tag]['frames_per_sec']:9.1f} f/s  "
                      f"{methods[tag]['retained_bytes']:8d} B retained")
        else:
            methods[name] = _bench_one(_make(name), chunk, repeats)
            methods[name]["backend"] = "xla"
            methods[name]["interpret"] = False
            print(f"[core] {name:13s} "
                  f"{methods[name]['frames_per_sec']:9.1f} f/s  "
                  f"{methods[name]['retained_bytes']:8d} B retained")

    # Self-describing trajectory: every row carries its speed relative
    # to the dense `epic` row, so an interpret-mode Pallas row can never
    # again read as a CPU regression without saying so.
    epic_ms = methods["epic"]["step_ms"]
    for m in methods.values():
        if not m.get("skipped"):
            m["speedup_vs_epic"] = round(epic_ms / m["step_ms"], 2)

    # The serving-runtime row (benchmarks/serve_bench.py) and the wire
    # ingest row (benchmarks/ingest_bench.py) live in the same
    # trajectory file but are produced by different benches; keep them
    # across core rewrites so `--only core` can't silently drop them.
    prev_methods = {}
    try:
        with open(os.path.join(REPO_ROOT, "BENCH_core.json")) as f:
            prev_methods = json.load(f).get("methods", {})
    except (OSError, json.JSONDecodeError):
        pass
    for row_name in ("serve", "serve[tiered]", "wire", "restore",
                     "overload"):
        if row_name in prev_methods:
            methods[row_name] = prev_methods[row_name]

    out = {
        "schema": "epic-core-bench-v9",
        "quick": quick,
        "protocol": {
            "n_frames": N_FRAMES,
            "frame_hw": FRAME,
            "patch": PATCH,
            "epic_capacity": CAPACITY,
            "sparse_prefilter_k": SPARSE_K,
            "sparse_patch_k": SPARSE_PATCH_K,
            "baseline_budget_patches": BUDGET,
            "interpret_rows_timed": interpret,
            "timing": f"best of {repeats} jitted steps, post-compile",
            "device": jax.devices()[0].platform,
        },
        "methods": methods,
        "wall_s": round(time.time() - t0, 1),
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "core_bench.json"), "w") as f:
        json.dump(out, f, indent=1)
    with open(os.path.join(REPO_ROOT, "BENCH_core.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv, interpret="--interpret" in sys.argv)
