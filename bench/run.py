"""Run one benchmark cell once and print its result line.

Usage, from the root of a checkout:

    python3 bench/run.py --workload glasses1m.walk.backfill --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window by the readers under ``bench/metrics/``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks`` (each number the configuration's path compares,
beside its limit).  Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 2; any other failure exits 1.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OUT = os.path.join(ROOT, ".bench")  # traces, removed once read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        chips = next(
            w["chips"] for w in json.load(f)["workloads"] if w["name"] == args.workload
        )
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"{args.workload} needs {chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 2

    from bench import harness
    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.say(
        f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache}"
    )
    bench = harness.spec()
    cell = harness.workload(bench, args.workload)
    e2e, per_layer = harness.metrics_of(bench, cell["name"])
    logdir = os.path.join(OUT, "trace", f"{cell['name']}.{args.seed}")
    shutil.rmtree(logdir, ignore_errors=True)
    try:
        out = harness.run(
            harness.config(bench, cell["config"]),
            harness.traffic(cell["traffic"]),
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            e2e=e2e, per_layer=per_layer, t_start=T_START, logdir=logdir,
        )
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    for name, c in out.checks.items():
        harness.say(f"check {name}: {c['value']} (limit {c['limit']})")
    line = {
        "correct": out.correct, "attempted": out.attempted, "failed": out.failed,
        "metrics": out.metrics, "device": out.device,
    }
    if out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = out.checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # the boundary: report and exit non-zero, no result
        traceback.print_exc()
        sys.exit(1)
