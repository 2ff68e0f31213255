"""Compile a cell's rung programs for a described TPU v5e, without a chip.

Usage, from the root of a checkout (``JAX_PLATFORMS=cpu`` is fine):

    python3 bench/compile_rungs.py --config glasses1m --streams 8

Builds the server a cell would build (``bench/harness.py``), lowers each
rung's full-capacity masked slot-pool step for one chip of a described
``v5e:2x2`` topology, compiles it, and prints the compiler's memory
analysis (argument and temporary bytes) and whether the step carries the
Pallas kernel (``tpu_custom_call``).  Nothing runs; no time is measured.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--streams", type=int, required=True)
    ap.add_argument("--rungs", type=int, nargs="*", help="default: the whole ladder")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.api import SensorChunk

    cfg = harness.config(harness.spec(), args.config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    srv, _ = harness.build_server(cfg, args.streams)
    f, cf, s = cfg["frame"], cfg["chunk_frames"], args.streams

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    states = on_chip(srv.pool.states)
    chunks = on_chip(SensorChunk(
        jnp.zeros((s, cf, f, f, 3)), jnp.zeros((s, cf, 4, 4)),
        jnp.zeros((s, cf, 2)), jnp.zeros((s, cf, f, f)),
    ))
    mask = jax.ShapeDtypeStruct((s,), jnp.bool_, sharding=chip)
    for k in args.rungs or cfg["k_ladder"]:
        t = time.monotonic()
        step = srv.pool._get_step(k, srv._rung_comp(k).step)
        lowered = step.lower(states, chunks, mask)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(
            f"{args.config} S={s} K={k}: arguments {mem.argument_size_in_bytes / 1e6:.1f} MB, "
            f"outputs {mem.output_size_in_bytes / 1e6:.1f} MB, temporaries "
            f"{mem.temp_size_in_bytes / 1e6:.1f} MB, aliased "
            f"{mem.alias_size_in_bytes / 1e6:.1f} MB, tpu_custom_call "
            f"{'tpu_custom_call' in compiled.as_text()} ({time.monotonic() - t:.1f} s)",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
