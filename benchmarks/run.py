"""Benchmark aggregator: one sub-benchmark per paper table/figure.

  core     -> core_bench        (frames/sec + retained bytes per method;
                                 also writes the repo-root BENCH_core.json
                                 perf trajectory)
  serve    -> serve_bench       (StreamServer steady-state frames/sec
                                 under 25% churn; merges the `serve` row
                                 into BENCH_core.json)
  ingest   -> ingest_bench      (wire-frame loadgen -> loopback ingest
                                 server latency percentiles; merges the
                                 `wire` row into BENCH_core.json)
  fault    -> fault_bench       (live-slot checkpoint save/restore + wire
                                 replay latency; merges the `restore` row
                                 into BENCH_core.json)
  table1   -> evu_accuracy      (EVU accuracy vs memory, 5 methods)
  figure6  -> energy_model      (system energy + memory, 7 systems)
  ablation -> compression_sweep (motion/bypass/depth ablations)
  roofline -> roofline          (40-cell dry-run roofline terms)

``python -m benchmarks.run [--quick] [--only NAME[,NAME...]]``
"""

from __future__ import annotations

import argparse
import json
import os
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--interpret", action="store_true",
        help="also time interpret-mode Pallas rows in the core bench "
             "(skipped by default: ~x100 wall time, not CPU speed)",
    )
    ap.add_argument(
        "--only", default=None,
        help="comma-separated sub-benchmark names (core,serve,ingest,"
             "fault,overload,table1,figure6,ablation,roofline)",
    )
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    summary = {}
    known = {
        "core", "serve", "ingest", "fault", "overload", "table1",
        "figure6", "ablation", "roofline",
    }
    selected = None if args.only is None else set(args.only.split(","))
    if selected is not None and not selected <= known:
        # Fail loudly: a typo'd/renamed name would otherwise run nothing
        # and exit 0 — turning the ci.sh --bench-smoke lane into a no-op.
        ap.error(
            f"unknown --only name(s) {sorted(selected - known)}; "
            f"known: {sorted(known)}"
        )

    def want(name):
        return selected is None or name in selected

    if want("core"):
        from benchmarks import core_bench

        r = core_bench.run(quick=args.quick, interpret=args.interpret)
        summary["core_frames_per_sec"] = {
            name: m["frames_per_sec"]
            for name, m in r["methods"].items()
            # the preserved `serve` row carries its own per-pool fields
            if not m.get("skipped") and "frames_per_sec" in m
        }
    if want("serve"):
        from benchmarks import serve_bench

        r = serve_bench.run(quick=args.quick)
        summary["serve_frames_per_sec"] = {
            name: p["frames_per_sec"] for name, p in r["pools"].items()
        }
    if want("ingest"):
        from benchmarks import ingest_bench

        r = ingest_bench.run(quick=args.quick)
        summary["ingest_p99_ms"] = {
            name: p["latency"]["total"]["p99_ms"]
            for name, p in r["pools"].items()
        }
    if want("fault"):
        from benchmarks import fault_bench

        r = fault_bench.run(quick=args.quick)
        summary["fault_restore_ms"] = r["restore_row"]["restore_ms"]
    if want("overload"):
        from benchmarks import overload_bench

        r = overload_bench.run(quick=args.quick)
        summary["overload_goodput_fps"] = {
            name: r["overload_row"][name]["goodput_fps"]
            for name in r["overload_row"]
            if name.startswith("x")
        }
    if want("figure6"):
        from benchmarks import energy_model

        r = energy_model.run()
        summary["figure6_energy"] = r["ratios"]
    if want("ablation"):
        from benchmarks import compression_sweep

        r = compression_sweep.run()
        summary["ablation"] = {
            "depth_int8_relative_diff": r["depth_ablation"]["relative_diff"]
        }
    if want("roofline"):
        from benchmarks import roofline

        try:
            rows = roofline.run()
        except FileNotFoundError as e:
            # The roofline needs the dry-run HLO artifact
            # (launch/dryrun.py writes results/dryrun.jsonl); skip
            # gracefully when it hasn't been generated on this machine.
            print(f"[roofline] skipped: {e}")
            summary["roofline_skipped"] = str(e)
            rows = []
        if rows:
            summary["roofline_cells"] = len(rows)
            summary["roofline_dominant"] = {}
            for row in rows:
                summary["roofline_dominant"].setdefault(row["dominant"], 0)
                summary["roofline_dominant"][row["dominant"]] += 1
    if want("table1"):
        from benchmarks import evu_accuracy

        r = evu_accuracy.run(quick=args.quick)
        summary["table1"] = r["results"]

    summary["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1)[:2000])


if __name__ == "__main__":
    main()
