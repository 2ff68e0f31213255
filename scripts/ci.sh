#!/usr/bin/env bash
# Tier-1 verification — the exact command CI and humans both run
# (see ROADMAP.md "Tier-1 verify").
#
#   scripts/ci.sh                     # full tier-1 suite (~10 min, 2 cores)
#   scripts/ci.sh --kernels           # Pallas kernels: interpret parity + v5e compiles
#   scripts/ci.sh --bench-smoke       # headless benchmarks/run.py --quick
#   scripts/ci.sh --serve             # serving-runtime suite + bench smoke
#   scripts/ci.sh --wire              # wire ingest-frontier suite
#   scripts/ci.sh --fault             # checkpoint/restore + crash soak lane
#   scripts/ci.sh --overload          # degradation + lossy-link soak lane
#   scripts/ci.sh --obs               # observability suite
#   scripts/ci.sh tests/test_api.py   # any extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

# Pin the platform and FORWARD it to every subprocess the tests spawn
# (tests/test_distribution.py, registry fresh-import tests, the sharded
# StreamPool device-count tests): a stripped env hangs at jax import
# while probing for accelerator plugins.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

if [[ "${1:-}" == "--kernels" ]]; then
  # Focused kernel lane: every Pallas kernel against its oracle in
  # interpret mode, plus the fused-TSRC and sparse-TRD parity suites
  # (v1 entry-side + v2 patch-side/fused∘sparse/adaptive-K), and the
  # reproject-match kernels + EPIC step compiled for a described v5e.
  shift
  exec python -m pytest -q tests/test_kernels.py tests/test_fused_tsrc.py \
    tests/test_sparse_tsrc.py tests/test_sparse_v2.py \
    tests/test_tpu_compile.py "$@"
fi

if [[ "${1:-}" == "--serve" ]]; then
  # Serving-runtime lane: the repro.serve suite (slotted admission/
  # eviction, per-stream adaptive-K parity, prefetch bit-identity,
  # 2-device shard_map subprocess, the churn soak) plus the tiered
  # suite (TieredPool migration/swap bitwise, rung scheduler, the
  # tiered-vs-flat soak), followed by a smoke of the serve bench —
  # refreshes the `serve` + `serve[tiered]` rows of BENCH_core.json —
  # and a zero-post-warmup-retrace assertion on both rows (the
  # benches count retraces via the pools' step_cache_sizes()).
  shift
  python -m pytest -q tests/test_serve.py tests/test_tiered_serve.py "$@"
  python -m benchmarks.run --quick --only serve
  exec python - <<'GUARD'
import json
import sys

d = json.load(open("BENCH_core.json"))
for name in ("serve", "serve[tiered]"):
    row = d["methods"].get(name)
    if row is None:
        sys.exit(f"BENCH_core.json: {name} row missing")
    n = row.get("post_warmup_retraces")
    if n != 0:
        sys.exit(f"BENCH_core.json: {name}.post_warmup_retraces = {n!r},"
                 " expected 0 (serving path retraced after warmup)")
print("[serve] zero post-warmup retraces across serve + serve[tiered]")
GUARD
fi

if [[ "${1:-}" == "--wire" ]]; then
  # Ingest-frontier lane: the wire codec round-trip/rejection
  # properties, loopback server -> StreamServer bitwise parity, trace
  # record/replay parity, and seeded loadgen determinism.
  shift
  exec python -m pytest -q tests/test_wire.py "$@"
fi

if [[ "${1:-}" == "--fault" ]]; then
  # Fault-tolerance lane: the checkpoint substrate properties (atomic
  # publish, damaged-step fallback, AsyncSaver error surfacing, stale
  # .tmp cleanup), the live-slot snapshot/restore suite with the
  # crash/fault-injection soaks (kill -> restore -> RESUME replay must
  # end bit-identical to the uninterrupted run, zero post-restore
  # retraces), then a smoke of the fault bench — lands/refreshes the
  # `restore` row of BENCH_core.json and guards its zero-retrace field.
  shift
  python -m pytest -q tests/test_substrates.py tests/test_fault_serve.py "$@"
  python -m benchmarks.run --quick --only fault
  exec python - <<'GUARD'
import json
import sys

d = json.load(open("BENCH_core.json"))
row = d["methods"].get("restore")
if row is None:
    sys.exit("BENCH_core.json: restore row missing (fault bench did not land)")
n = row.get("post_restore_retraces")
if n != 0:
    sys.exit(f"BENCH_core.json: restore.post_restore_retraces = {n!r}, "
             "expected 0 (restore retraced the serving path)")
print(f"[fault] restore row ok: restore={row['restore_ms']}ms "
      f"replay={row['replay_chunks']} chunks @ "
      f"{row['replay_per_chunk_ms']}ms, zero post-restore retraces")
GUARD
fi

if [[ "${1:-}" == "--overload" ]]; then
  # Overload-resilience lane: the degradation-controller suite
  # (hysteresis levels, rung caps, stale shed, tier deferral), the
  # seeded lossy-link soaks (drop/dup/reorder/corrupt/truncate through
  # FaultyTransport must still converge bit-identically), and the
  # overload soak (deterministic shed, bounded queue wait, zero
  # retraces across level transitions) — then a smoke of the overload
  # bench, which lands/refreshes the `overload` row of BENCH_core.json
  # and guards its determinism + zero-retrace fields.
  shift
  python -m pytest -q tests/test_overload.py "$@"
  python -m benchmarks.run --quick --only overload
  exec python - <<'GUARD'
import json
import sys

d = json.load(open("BENCH_core.json"))
row = d["methods"].get("overload")
if row is None:
    sys.exit("BENCH_core.json: overload row missing "
             "(overload bench did not land)")
if row.get("deterministic") is not True:
    sys.exit(f"BENCH_core.json: overload.deterministic = "
             f"{row.get('deterministic')!r} — same-seed overload runs "
             "diverged (shed/degrade trajectory is nondeterministic)")
n = row.get("post_warmup_retraces")
if n != 0:
    sys.exit(f"BENCH_core.json: overload.post_warmup_retraces = {n!r}, "
             "expected 0 (a degradation level transition retraced)")
x = row.get("x4", {})
print(f"[overload] row ok: x4 goodput={x.get('goodput_fps')} f/s, "
      f"shed={x.get('shed_fraction')}, deterministic, zero retraces")
GUARD
fi

if [[ "${1:-}" == "--obs" ]]; then
  # Observability lane: the repro.obs suite (metrics registry units,
  # histogram merge/percentile pins, flight-recorder Chrome-trace
  # validity, chunk records and the profile clock, server and wire
  # span integration, STATUS over loopback + TCP, three-view counter
  # consistency after a lossy overload soak, k-trajectory ring bound).
  shift
  exec python -m pytest -q tests/test_obs.py "$@"
fi

if [[ "${1:-}" == "--bench-smoke" ]]; then
  # Headless perf-path smoke (~35 s): the quick core throughput sweep
  # (every compressor row incl. epic[sparse]; interpret-mode Pallas
  # rows are skipped — pass --interpret to time them) + the figure-6
  # energy model, with JAX_PLATFORMS forwarded above — a broken hot
  # path is caught here rather than discovered at bench time.
  # Refreshes BENCH_core.json, then guards the sparse-TRD win: the
  # epic[sparse] row regressing below 2.5x dense fails the lane.  The
  # slow lanes (table1/ablation, several minutes each) stay on demand:
  # `python -m benchmarks.run --quick`.
  shift
  before_stamp=$(stat -c %Y BENCH_core.json 2>/dev/null || echo absent)
  python -m benchmarks.run --quick --only core,figure6 "$@"
  after_stamp=$(stat -c %Y BENCH_core.json 2>/dev/null || echo absent)
  if [[ "$after_stamp" == "absent" || "$after_stamp" == "$before_stamp" ]]; then
    # Pass-through args (e.g. a second --only without "core") can keep
    # the core bench from running; guarding stale numbers would print a
    # bogus ok.
    echo "[bench-smoke] core bench did not refresh BENCH_core.json;" \
         "skipping the sparse-TRD guard"
    exit 0
  fi
  # The ingest smoke runs after the stamp check (it rewrites
  # BENCH_core.json too, which would defeat the staleness detection).
  python -m benchmarks.run --quick --only ingest
  exec python - <<'GUARD'
import json
import sys

d = json.load(open("BENCH_core.json"))
row = d["methods"]["epic[sparse]"]
speedup = row.get("speedup_vs_epic")
floor = 2.5
if row.get("skipped") or speedup is None:
    sys.exit("BENCH_core.json: epic[sparse] row missing a speedup")
if speedup < floor:
    sys.exit(
        f"perf regression: epic[sparse].speedup_vs_epic = {speedup} "
        f"< {floor} (dense {d['methods']['epic']['step_ms']} ms vs "
        f"sparse {row['step_ms']} ms)"
    )
print(f"[bench-smoke] sparse-TRD guard ok: {speedup}x >= {floor}x")

wire = d["methods"].get("wire")
if wire is None:
    sys.exit("BENCH_core.json: wire row missing (ingest bench did not land)")
for pool in ("pool4", "pool16"):
    p99 = wire.get(pool, {}).get("p99_ms")
    if p99 is None:
        sys.exit(f"BENCH_core.json: wire.{pool} has no p99 latency")
print("[bench-smoke] wire ingest row ok: p99 "
      f"pool4={wire['pool4']['p99_ms']}ms pool16={wire['pool16']['p99_ms']}ms")

# Tiered-serving guard: the serve[tiered] row (refreshed by
# `ci.sh --serve`, preserved across core rewrites) must keep its
# low-occupancy win — 4 active streams on a pool-16 capacity at
# >= 2x the flat pool.
tiered = d["methods"].get("serve[tiered]")
if tiered is None:
    sys.exit("BENCH_core.json: serve[tiered] row missing "
             "(run scripts/ci.sh --serve to land it)")
tfloor = 2.0
tspeed = tiered.get("occ4_speedup")
if tspeed is None:
    sys.exit("BENCH_core.json: serve[tiered] row has no occ4_speedup")
if tspeed < tfloor:
    sys.exit(
        f"perf regression: serve[tiered].occ4_speedup = {tspeed} < "
        f"{tfloor} (flat {tiered.get('occ4_flat_frames_per_sec')} f/s "
        f"vs tiered {tiered.get('occ4_tiered_frames_per_sec')} f/s)"
    )
print(f"[bench-smoke] tiered serving guard ok: {tspeed}x >= {tfloor}x "
      "at 4/16 occupancy")

# Fault-tolerance guard: the restore row (refreshed by
# `ci.sh --fault`, preserved across core rewrites) must be present and
# retrace-free — a missing row means the checkpoint/restore path never
# landed its numbers.
restore = d["methods"].get("restore")
if restore is None:
    sys.exit("BENCH_core.json: restore row missing "
             "(run scripts/ci.sh --fault to land it)")
if restore.get("post_restore_retraces") != 0:
    sys.exit("BENCH_core.json: restore.post_restore_retraces = "
             f"{restore.get('post_restore_retraces')!r}, expected 0")
print(f"[bench-smoke] restore row ok: restore={restore['restore_ms']}ms, "
      "zero post-restore retraces")

# Overload guard: the overload row (refreshed by `ci.sh --overload`,
# preserved across core rewrites) must be present, deterministic and
# retrace-free — nondeterministic shedding would silently break the
# reproducibility contract every soak relies on.
overload = d["methods"].get("overload")
if overload is None:
    sys.exit("BENCH_core.json: overload row missing "
             "(run scripts/ci.sh --overload to land it)")
if overload.get("deterministic") is not True:
    sys.exit("BENCH_core.json: overload.deterministic = "
             f"{overload.get('deterministic')!r} — same-seed overload "
             "runs diverged")
if overload.get("post_warmup_retraces") != 0:
    sys.exit("BENCH_core.json: overload.post_warmup_retraces = "
             f"{overload.get('post_warmup_retraces')!r}, expected 0")
print("[bench-smoke] overload row ok: "
      f"x4 shed={overload.get('x4', {}).get('shed_fraction')}, "
      "deterministic, zero retraces")
GUARD
fi

exec python -m pytest -x -q "$@"
