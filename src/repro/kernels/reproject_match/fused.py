"""Fused Pallas TSRC step: warp + match + thresholds + update mask.

The plain ``pallas`` backend computes per-entry (diff, coverage, bbox)
and leaves the spatial association to XLA: ``tsrc_step`` materializes a
dense (N entries x M patches) overlap matrix with
``geo.bbox_overlap_fraction`` and thresholds it against the current
frame's patch grid.  On the EPIC accelerator all of that happens inside
the reprojection engine (paper Section 4.1.1); this kernel mirrors that
fusion on TPU — each grid step owns one DC-buffer entry and emits, in
one pass over data already resident in VMEM/registers:

  * the packed ``[diff, coverage, bbox]`` row (bitwise identical to the
    ``pallas`` backend — both run :func:`kernel._entry_scores`),
  * the entry's **overlap row** (bbox-overlap >= ``o_min`` per frame
    patch; the accelerator's prefilter bits), and
  * the entry's **update-mask row**: overlap AND the occlusion /
    consistency thresholds ``diff <= tau`` / ``coverage >= c_min`` —
    the per-(entry, patch) match feasibility TSRC feeds to
    ``newest_match``.

The patch grid is implicit (row-major ``(H//P) x (W//P)``, matching
``tsrc.extract_patches``), so the rows are cheap ``broadcasted_iota``
arithmetic — no extra memory traffic.

Registration: the standard-signature backend (diff/coverage/bbox only)
registers under ``"fused"``; the whole-step entry point is attached as
its ``fused_match`` capability attribute, which ``tsrc_step`` picks up
via ``getattr`` — neither the op dispatcher in ``ops.py`` nor the TSRC
step body needs editing for a new fused backend to slot in.

Candidate-slab composition (sparse TRD v2): the entry point is shape-
polymorphic over its leading entry axis, so the sparse prefilter feeds
it the gathered ``(K, ...)`` candidate slabs directly — fused ∘ sparse,
one kernel pass per *candidate* instead of per entry, with the mask
rows bitwise the thresholded ``"pallas"`` scores on the same slabs
(``tests/test_sparse_v2.py``).  The former "prefilter takes precedence
over fused_match" carve-out in ``tsrc_step`` is gone.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.api.registry import register_backend
from repro.core import geometry as geo
from repro.kernels.reproject_match.kernel import (
    ROWS,
    _entry_scores,
    _put_row,
    _score_row,
    _scores,
    fold_vmap,
    kernel_operands,
    launch,
    on_platform,
    pad_entries,
)

Array = jax.Array


def _fused_tsrc_kernel(
    intr_ref, origin_ref, trel_ref, rgb_ref, depth_ref, frame_ref,
    out_ref,  # (8, 8) packed [diff, coverage, bbox(4), pad(2)] rows
    ovok_ref,  # (8, M) float 0/1 — bbox overlap >= o_min per patch
    match_ref,  # (8, M) float 0/1 — overlap AND diff/coverage thresholds
    band_ref, sem,
    *,
    patch: int,
    window: int,
    frame_h: int,
    frame_w: int,
    tau: float,
    o_min: float,
    c_min: float,
):
    r = pl.program_id(1) % ROWS
    scores = _entry_scores(
        intr_ref, origin_ref, trel_ref, rgb_ref, depth_ref, frame_ref,
        band_ref, sem,
        patch=patch, window=window, frame_h=frame_h, frame_w=frame_w,
    )
    diff, coverage, vmin, umin, vmax, umax = scores
    _put_row(out_ref, r, _score_row(scores))

    # --- Spatial association against the implicit frame patch grid. --------
    gx = frame_w // patch
    gy = frame_h // patch
    m = gy * gx
    jj = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1).astype(jnp.float32)
    gyi = jnp.floor(jj / gx)  # exact: small integers
    pv0 = gyi * patch
    pu0 = (jj - gyi * gx) * patch
    pv1 = pv0 + patch
    pu1 = pu0 + patch
    # Same formula as geo.bbox_overlap_fraction (kept in lockstep so the
    # fused path and the composed path agree bit for bit).
    iv = jnp.maximum(0.0, jnp.minimum(vmax, pv1) - jnp.maximum(vmin, pv0))
    iu = jnp.maximum(0.0, jnp.minimum(umax, pu1) - jnp.maximum(umin, pu0))
    overlap = iv * iu / float(patch * patch)

    ovok = overlap >= o_min
    entry_ok = (diff <= tau) & (coverage >= c_min)
    _put_row(ovok_ref, r, jnp.where(ovok, 1.0, 0.0))
    _put_row(match_ref, r, jnp.where(entry_ok & ovok, 1.0, 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("window", "tau", "o_min", "c_min", "interpret"),
)
def reproject_match_fused(
    entry_rgb: Array,  # (N, P, P, 3)
    entry_depth: Array,  # (N, P, P)
    entry_origin: Array,  # (N, 2)
    t_rel: Array,  # (N, 4, 4)
    frame: Array,  # (H, W, 3)
    intr: geo.Intrinsics,
    *,
    window: int = 64,
    tau: float = 0.08,
    o_min: float = 0.5,
    c_min: float = 0.6,
    interpret: bool,
) -> Tuple[Array, Array, Array, Array, Array]:
    """Fused TSRC match: one kernel pass per DC-buffer entry.

    Returns:
      diff (N,), coverage (N,), bbox (N, 4),
      pair_ok (N, M) bool — per-(entry, patch) update-mask feasibility
        (thresholds applied in-kernel; the caller still ANDs buffer
        validity and saliency),
      overlap_ok (N, M) bool — the bare spatial-overlap prefilter bits
        (drives the energy model's full-check counter).

    ``M`` is the frame's patch count ``(H // P) * (W // P)`` in
    ``tsrc.extract_patches`` row-major order.
    """
    n, p = entry_rgb.shape[0], entry_rgb.shape[1]
    h, w = frame.shape[0], frame.shape[1]
    m = (h // p) * (w // p)
    args = pad_entries(entry_rgb, entry_depth, entry_origin, t_rel, ROWS)
    kernel = functools.partial(
        _fused_tsrc_kernel,
        patch=p,
        window=window,
        frame_h=h,
        frame_w=w,
        tau=tau,
        o_min=o_min,
        c_min=c_min,
    )

    def batched(*operands):
        return tuple(
            launch(
                kernel, operands, tile=1, out_cols=(8, m, m), window=window,
                interpret=interpret,
            )
        )

    out, ovok, match = fold_vmap(batched)(*kernel_operands(*args, frame, intr))
    diff, coverage, bbox = _scores(out, n)
    return diff, coverage, bbox, match[:n] > 0.5, ovok[:n] > 0.5


def fused_match(
    entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
    *, window, tau, o_min, c_min,
):
    """:func:`reproject_match_fused`, compiled on TPU and interpreted
    elsewhere (see :func:`~repro.kernels.reproject_match.kernel.
    on_platform`)."""
    return on_platform(
        reproject_match_fused,
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
        window=window, tau=tau, o_min=o_min, c_min=c_min,
    )


@register_backend("fused")
def _fused_backend(
    entry_rgb, entry_depth, entry_origin, t_rel, frame, intr, *, window
):
    """Standard reproject-match contract (diff, coverage, bbox) served
    by the fused kernel — thresholds don't affect these outputs."""
    diff, coverage, bbox, _, _ = fused_match(
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
        window=window, tau=0.08, o_min=0.5, c_min=0.6,
    )
    return diff, coverage, bbox


# Capability attribute: tsrc_step detects this and runs the whole match
# (thresholds + update mask) as one kernel — see core/tsrc.py.
_fused_backend.fused_match = fused_match
