"""The control of the correctness check: the reference in bfloat16, in
the program's place, compared as a run compares the program.

Usage, from the root of a checkout on a machine with the cell's chips:

    python3 bench/control.py --workload glasses1m.walk.backfill \
        --seeds 11 12 13 --chunks 40

For each seed it renders the cell's clip bank, takes the streams a run
would sample, and feeds each of them ``--chunks`` chunks of its traffic
through the float32 reference and through the same reference computed
in bfloat16 (the precision below the configuration's).  It prints the
numbers a run compares (the share of retained entries that differ,
exact counters that differ, the gap in inserted patches per million) for the bfloat16 session
against the float32 one: the upper readings from which the limits of
``harness.LIMITS`` are set.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--chunks", type=int, required=True)
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from bench import harness, reference

    bench = harness.spec()
    cell = harness.workload(bench, args.workload)
    cfg = harness.config(bench, cell["config"])
    mix = harness.traffic(cell["traffic"])
    p = reference.Params.of(cfg)
    for seed in args.seeds:
        t = time.monotonic()
        bank = harness.make_bank(seed, cfg, mix)
        rng = np.random.default_rng(seed)
        sids = [0] + sorted(
            1 + rng.choice(mix["streams"] - 1, size=mix["sample_streams"] - 1, replace=False)
        )
        total = {"entries_off": 0, "entries": 0, "counters_off": 0, "inserted_gap": 0, "inserted": 0}
        for sid in sids:
            chunks = list(harness.stream_chunks(bank, sid, list(range(args.chunks)), mix["streams"]))
            want = reference.run_stream(p, cfg["k_ladder"], chunks)
            got = reference.run_stream(p, cfg["k_ladder"], chunks, dtype=jnp.bfloat16)
            d = reference.compare(got, want)
            total["entries_off"] += d.entries
            total["counters_off"] += d.counters
            total["inserted_gap"] += d.inserted_gap
            total["entries"] += want.retained["valid"].shape[0]
            total["inserted"] += want.counters["n_inserted"]
            harness.say(f"seed {seed} stream {sid}: " + "; ".join(d.lines)[:300])
        print(json.dumps({
            "seed": seed, "workload": args.workload, "chunks": args.chunks, **total,
            "entries_off_pct": 100.0 * total["entries_off"] / total["entries"],
            "inserted_gap_ppm": 1e6 * total["inserted_gap"] / max(total["inserted"], 1),
            "seconds": round(time.monotonic() - t, 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
