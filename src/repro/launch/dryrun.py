import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
#   init, and the production meshes below need 512 placeholder devices.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the FULL published config (ShapeDtypeStruct stand-ins only —
     no parameter is ever allocated);
  2. pjit-lowers the right entry point (train_step / prefill / decode) with
     the production shardings from launch/sharding.py;
  3. ``.compile()``s it — sharding mismatches, unsupported collectives and
     partitioning bugs fail HERE;
  4. records memory_analysis / cost_analysis / per-collective byte counts
     (parsed from the post-SPMD optimized HLO) to a JSONL that
     benchmarks/roofline.py consumes.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--multi-pod-only] [--skip-existing]
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_shapes
from repro.launch.mesh import make_production_mesh
from repro.models import build_model

RESULTS = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results"
)

def sharded_bytes(tree: Any, specs: Any, mesh) -> int:
    """Exact per-device resident bytes for a spec'd pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    sflat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )
    total = 0
    for (_, leaf), spec in zip(flat, sflat):
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= mesh.shape[a]
        total += leaf.size * leaf.dtype.itemsize // denom
    return total


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: Optional[Dict[str, Any]] = None):
    """Returns (lowered, aux dict with spec'd byte counts)."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = next(s for s in get_shapes(arch) if s.name == shape_name)
    if shape.skip:
        return None, {"skipped": shape.skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    from repro.launch import serve as SV
    from repro.launch import sharding as S
    from repro.launch import train as TR
    from repro.optim.adamw import AdamWConfig

    aux: Dict[str, Any] = {}
    pshape = model.param_spec()
    pspecs = S.param_specs(cfg, pshape, mesh)
    aux["param_bytes_per_device"] = sharded_bytes(pshape, pspecs, mesh)
    aux["param_count"] = sum(l.size for l in jax.tree.leaves(pshape))

    if shape.kind == "train":
        mdt = jnp.dtype(cfg.opt_moment_dtype)
        from repro.optim import adamw as _adamw

        oshape = jax.eval_shape(
            lambda p: TR.cast_moments(_adamw.init(p), mdt), pshape
        )
        ospecs = S.opt_specs(cfg, pshape, mesh)
        aux["opt_bytes_per_device"] = sharded_bytes(oshape, ospecs, mesh)
        batch = model.batch_spec(shape)
        step_fn, _ = TR.jit_train_step(
            model, mesh, AdamWConfig(), shape_spec=shape,
            moment_dtype=mdt, accum=cfg.train_accum,
        )
        with jax.set_mesh(mesh):
            lowered = step_fn.lower(
                pshape, oshape, batch, jax.ShapeDtypeStruct((), jnp.int32)
            )
    elif shape.kind == "prefill":
        batch = model.batch_spec(shape)
        fn, _ = SV.jit_prefill(model, mesh, shape)
        with jax.set_mesh(mesh):
            lowered = fn.lower(pshape, batch)
    else:  # decode
        b = shape.global_batch
        sshape = model.serve_spec(b, shape.seq_len)
        sspecs = S.serve_specs(cfg, sshape, mesh, b)
        aux["cache_bytes_per_device"] = sharded_bytes(sshape, sspecs, mesh)
        fn, _ = SV.jit_decode_step(model, mesh, shape)
        with jax.set_mesh(mesh):
            lowered = fn.lower(
                pshape,
                sshape,
                model.token_spec(b),
                jax.ShapeDtypeStruct((), jnp.int32),
            )
    return lowered, aux


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, *, verbose: bool = True,
    overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "ok": False,
    }
    if overrides:
        rec["overrides"] = overrides
    t0 = time.time()
    try:
        lowered, aux = lower_cell(arch, shape_name, multi_pod, overrides)
        rec.update(aux)
        if lowered is None:
            rec["ok"] = True
            rec["skipped"] = aux["skipped"]
            return rec
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)

        mem = compiled.memory_analysis()
        if mem is not None:
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                v = getattr(mem, k, None)
                if v is not None:
                    rec[k] = int(v)
        cost = compiled.cost_analysis()
        if cost:
            c = cost[0] if isinstance(cost, (list, tuple)) else cost
            rec["flops"] = float(c.get("flops", -1))
            rec["bytes_accessed"] = float(c.get("bytes accessed", -1))
        from repro.launch.hloparse import analyze_collectives

        rec["collectives"] = analyze_collectives(compiled.as_text())
        rec["ok"] = True
        if verbose:
            print(
                f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
                f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s, "
                f"flops={rec.get('flops', 0):.3e}, "
                f"coll={rec['collectives']['total_bytes']:.3e}B "
                f"wire={rec['collectives']['wire_bytes']:.3e}B)"
            )
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAIL {e}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the optimized recipes (benchmarks/opt_config)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    out_path = args.out or os.path.join(
        os.path.abspath(RESULTS),
        "dryrun_opt.jsonl" if args.opt else "dryrun.jsonl",
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    done = set()
    if args.skip_existing and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    cells = []
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    for arch in archs:
        for shape in get_shapes(arch):
            if args.shape and shape.name != args.shape:
                continue
            for mp in (False, True):
                if args.mesh == "pod" and mp:
                    continue
                if args.mesh == "multipod" and not mp:
                    continue
                cells.append((arch, shape.name, mp))

    n_fail = 0
    with open(out_path, "a") as f:
        for arch, shape_name, mp in cells:
            mesh_name = "2x16x16" if mp else "16x16"
            if (arch, shape_name, mesh_name) in done:
                continue
            ov = None
            if args.opt:
                from benchmarks.opt_config import overrides_for

                kind = next(
                    s for s in get_shapes(arch) if s.name == shape_name
                ).kind
                ov = overrides_for(arch, kind)
            rec = run_cell(arch, shape_name, mp, overrides=ov)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            if not rec["ok"]:
                n_fail += 1
    print(f"[dryrun] finished; {n_fail} failures -> {out_path}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
