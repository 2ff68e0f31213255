"""Dispatching wrapper for the reproject-match op.

Backends are looked up by name in :mod:`repro.api.registry` (so
``TSRCConfig.backend`` is a registry key, not a string compared here):

``backend="ref"`` — pure-jnp oracle (default; used by the streaming pipeline
on CPU and inside SPMD lowering, where a TPU Pallas custom call cannot lower).

``backend="pallas"`` — the Pallas TPU kernel (``kernel.py``): compiled
where the program is lowered for TPU, interpreted on CPU (the test path).

``backend="pallas_tiled"`` — the entry-tiled Pallas kernel (``TILE_N``
entries per grid step); same per-entry math as ``pallas``, but the grid-step
overhead is amortised — the right layout for the small candidate counts the
sparse-TRD prefilter produces (``TSRCConfig.prefilter_k``).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax

from repro.api.registry import get_backend, register_backend
from repro.core import geometry as geo
from repro.kernels.reproject_match.kernel import (
    on_platform,
    reproject_match_pallas,
    reproject_match_pallas_tiled,
)
from repro.kernels.reproject_match.ref import reproject_match_ref

Array = jax.Array


@register_backend("ref")
def _ref_backend(
    entry_rgb, entry_depth, entry_origin, t_rel, frame, intr, *, window
):
    return reproject_match_ref(
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr, window
    )


@register_backend("pallas")
def _pallas_backend(
    entry_rgb, entry_depth, entry_origin, t_rel, frame, intr, *, window
):
    return on_platform(
        reproject_match_pallas,
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
        window=window,
    )


@register_backend("pallas_tiled")
def _pallas_tiled_backend(
    entry_rgb, entry_depth, entry_origin, t_rel, frame, intr, *, window
):
    return on_platform(
        reproject_match_pallas_tiled,
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
        window=window,
    )


@partial(jax.jit, static_argnames=("window", "backend"))
def reproject_match(
    entry_rgb: Array,
    entry_depth: Array,
    entry_origin: Array,
    t_rel: Array,
    frame: Array,
    intr: geo.Intrinsics,
    *,
    window: int = 64,
    backend: str = "ref",
) -> Tuple[Array, Array, Array]:
    """Warp buffered patches into the current view and score redundancy.

    Args:
      entry_rgb: (N, P, P, 3) buffered patch pixels I_c.
      entry_depth: (N, P, P) buffered per-pixel depth d_c.
      entry_origin: (N, 2) patch top-left (row, col) in the source frame.
      t_rel: (N, 4, 4) source->current camera transforms.
      frame: (H, W, 3) current frame F_t.
      intr: camera intrinsics.
      window: sampling window side (op semantics; see ref.py).
      backend: registry name ("ref" | "pallas" | anything registered
        via repro.api.registry.register_backend).

    Returns:
      diff (N,), coverage (N,), bbox (N, 4).
    """
    fn = get_backend(backend)
    return fn(
        entry_rgb, entry_depth, entry_origin, t_rel, frame, intr,
        window=window,
    )
