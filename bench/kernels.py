"""The Pallas kernels the benchmark times, and the work each has to do.

Kernel time is the summed device time of the trace events whose name
(or a text stat) holds one of these names: the jitted entry points of
the three Pallas backends of ``src/repro/kernels/reproject_match/``
(``fused.py``, ``kernel.py``), as the TPU trace names their calls.  The
time so includes the entry point's operand relayout (the frame's
transpose and pad to whole tiles) beside the Mosaic kernel itself, so
the share is, if anything, read low.

The work is the op contract of the reproject-match reference
(``src/repro/kernels/reproject_match/ref.py``), counted per candidate
entry scored on one frame, whatever implements it:

* bytes: the entry's pixels (P x P x 3 float32) and depth (P x P), its
  origin (2) and transform (16), the ``window`` x ``window`` x 3 frame
  region it is sampled from, and its outputs (diff, coverage, a 4-value
  box), all float32 and each read or written once;
* operations: per entry pixel, the lift (6), the rigid transform (18),
  the projection (6), the bilinear weights and sample (2 + 8 + 21), the
  absolute difference and channel mean (9) and the masked sum (1): 71;
  plus the four corners' lift, transform and projection (30 each).
"""

from __future__ import annotations

from typing import Dict, Tuple

REPROJECT_MATCH = (
    "reproject_match_fused", "reproject_match_pallas", "reproject_match_pallas_tiled",
)

OPS_PER_PIXEL = 71
OPS_PER_CORNER = 30
F32 = 4


def reproject_match_work(cfg: Dict, candidates: int) -> Tuple[float, float]:
    """``(operations, bytes)`` for ``candidates`` entry-frame scorings."""
    p, w = cfg["patch"], cfg["window"]
    px = p * p
    nbytes = F32 * (px * 3 + px + 2 + 16 + w * w * 3 + 6)
    ops = OPS_PER_PIXEL * px + 4 * OPS_PER_CORNER
    return float(ops * candidates), float(nbytes * candidates)
