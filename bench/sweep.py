"""Find the live knee: short open-loop runs of one configuration at a
falling number of streams.

Usage, from the root of a checkout on a machine with one chip:

    python3 bench/sweep.py --config glasses1m --traffic walk.live.s6 \
        --streams 12 10 8 6 --seconds 10 --seed 5

Each count runs the harness once, in this process, with the traffic
mix's ``streams`` replaced.  One JSON line per count: refused chunks,
chunks missing at the end of the grace, the latency median and 95th
percentile, and the 95th percentile of the first and the last third of
the window's chunks (a growing backlog shows as a later third slower
than the first).  The knee is the highest count with no refused chunk
and no growing backlog.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    import jax

    from bench import harness, stats
    from repro.runtime.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("the sweep needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.spec()
    cfg = harness.config(bench, args.config)
    e2e = [{"name": n, "unit": ""} for n in ("frames_per_s", "setup_s")]
    for s in args.streams:
        mix = dict(harness.traffic(args.traffic), streams=s, warmup_ticks=2 * s)
        t = time.monotonic()
        out = harness.run(
            cfg, mix, seed=args.seed, seconds=args.seconds, trace=False, e2e=e2e,
            per_layer=[], t_start=t, logdir="",
        )
        lat = out.latencies
        xs = [x for _, x in lat]
        out.metrics.update(
            {f"latency_p{q}_ms": {"value": 1e3 * stats.percentile(xs, q)} for q in (50, 95)} if xs else {}
        )
        third = max(1, len(lat) // 3)
        first = [x for _, x in sorted(lat)[:third]]
        last = [x for _, x in sorted(lat)[-third:]]
        print(json.dumps({
            "streams": s, "correct": out.correct, "attempted": out.attempted,
            "failed": out.failed,
            "frames_per_s": out.metrics["frames_per_s"]["value"],
            "p50_ms": out.metrics["latency_p50_ms"]["value"] if "latency_p50_ms" in out.metrics else None,
            "p95_ms": out.metrics["latency_p95_ms"]["value"] if "latency_p95_ms" in out.metrics else None,
            "p95_first_third_ms": 1e3 * stats.percentile(first, 95) if first else None,
            "p95_last_third_ms": 1e3 * stats.percentile(last, 95) if last else None,
            "refused": out.failed, "seconds": round(time.monotonic() - t, 1),
        }, default=lambda v: None if isinstance(v, float) and math.isinf(v) else v), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
