"""Generate the pre-refactor golden outputs for stage-graph parity tests.

Run ONCE against the monolithic (pre-stage-graph) pipeline and commit the
resulting ``stage_graph_golden.npz``; ``tests/test_stages.py`` then asserts
the stage-graph re-expression of EPIC and all four baselines reproduces
these outputs bit for bit.

  PYTHONPATH=src python tests/goldens/generate_stage_goldens.py

Refreshed with the sparse-TRD PR: all state leaves and match/insert stats
are unchanged bit for bit; only the EPIC ``n_bbox_checks``/``n_full_checks``
counters moved (now measured against the pre-insert buffer the TRD actually
ran on, instead of the permuted post-insert occupancy) and the
``n_prefilter_overflow`` leaf was appended (0 on the dense path pinned here).

Refreshed again with Sparse TRD v2: every pre-existing leaf is unchanged
bit for bit; only the ``n_patch_overflow`` / ``n_patch_checked`` counter
leaves were appended (both 0 on the dense path pinned here).

Regenerated for JAX 0.9, whose ``jax.random`` streams differ from the
version the goldens were first made with: the synthetic input chunk (and
the randomly initialised HIR weights) moved, not the pipeline.  The file
now stores what the outputs were computed from — the input chunk under
``input/{frames,poses,gazes,depth}`` and the HIR weights under
``input/hir/<i>`` — and the tests read them from here, so a change of
random-number generator can no longer move the goldens.
"""

import os

import jax
import numpy as np

from repro import api
from repro.core import hir
from repro.core import pipeline as P
from repro.data import synthetic as SYN

FRAME = 64
PATCH = 16
N_FRAMES = 40
HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "stage_graph_golden.npz")


def stream():
    scfg = SYN.StreamConfig(n_frames=N_FRAMES, hw=(FRAME, FRAME), n_obj=4)
    s, _ = SYN.generate_stream(jax.random.PRNGKey(0), scfg)
    return s


def epic_cfg():
    return P.EPICConfig(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=32,
        tau=0.10, gamma=0.015, theta=8, window=16,
    )


def main():
    s = stream()
    chunk = api.SensorChunk(s.frames, s.poses, s.gazes, s.depth)
    hir_params = hir.init_params(jax.random.PRNGKey(7))
    out = {
        f"input/{name}": np.asarray(getattr(chunk, name))
        for name in ("frames", "poses", "gazes", "depth")
    }
    for i, leaf in enumerate(jax.tree.leaves(hir_params)):
        out[f"input/hir/{i}"] = np.asarray(leaf)

    def record(tag, state, stats):
        for i, leaf in enumerate(jax.tree.leaves(state)):
            out[f"{tag}/state/{i}"] = np.asarray(leaf)
        for i, leaf in enumerate(jax.tree.leaves(stats)):
            out[f"{tag}/stats/{i}"] = np.asarray(leaf)

    # EPIC, oracle mode (gt depth, all-salient).
    comp = api.get_compressor("epic")(epic_cfg())
    state, stats = jax.jit(comp.step)(comp.init(), chunk)
    record("epic_oracle", state, stats)

    # EPIC with a (randomly initialised) HIR saliency model — exercises
    # the saliency stage's learned path.
    models = P.EPICModels(depth_params=None, hir_params=hir_params)
    comp = api.get_compressor("epic")(epic_cfg(), models)
    state, stats = jax.jit(comp.step)(comp.init(), chunk)
    record("epic_hir", state, stats)

    # The four streaming baselines at a bounded budget (and FV unbounded).
    for name, budget in (("fv", -1), ("sd", 64), ("td", 64), ("gc", 64)):
        comp = api.get_compressor(name)(api.BaselineConfig(
            frame_hw=(FRAME, FRAME), patch=PATCH,
            budget_patches=budget, n_frames=N_FRAMES,
        ))
        state, stats = jax.jit(comp.step)(comp.init(), chunk)
        record(name, state, stats)

    np.savez_compressed(OUT, **out)
    print(f"wrote {OUT} ({os.path.getsize(OUT) / 1e6:.2f} MB, "
          f"{len(out)} arrays)")


if __name__ == "__main__":
    main()
