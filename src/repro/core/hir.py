"""Human Intention Based Refinement (HIR) module (EPIC paper, Section 3.3).

A lightweight 3-layer CNN predicts a *binary saliency map* over the patch
grid of each frame, conditioned on the user's gaze location. This is the
Spatial Redundancy Detection (SRD) stage: only salient patches proceed to the
temporal redundancy check / DC-buffer storage.

Design notes (paper-faithful):
* exactly 3 conv layers;
* gaze enters as a Gaussian heatmap channel concatenated to the RGB input
  (the paper conditions selection on the gaze location q_t);
* output is one logit per patch; the binary map is ``logit > 0``;
* trained with BCE against task-relevance labels (the paper fine-tunes on
  1000 held-out questions per dataset; we train on synthetic ground truth).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Dict[str, Array]

HIR_INPUT = 64  # HIR operates on the same 64x64 downsampled view as depth


def _init_conv(key, kh, kw, cin, cout):
    std = math.sqrt(2.0 / (kh * kw * cin))
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def init_params(key: Array) -> Params:
    """3-layer CNN: 4ch (RGB+gaze) -> 16 -> 32 -> 1."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": _init_conv(k1, 3, 3, 4, 16),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": _init_conv(k2, 3, 3, 16, 32),
        "b2": jnp.zeros((32,), jnp.float32),
        "w3": _init_conv(k3, 3, 3, 32, 1),
        "b3": jnp.zeros((1,), jnp.float32),
    }


def gaze_heatmap(gaze_uv: Array, size: int, frame_hw: tuple,
                 sigma_frac: float = 0.08) -> Array:
    """Gaussian bump centred at the gaze location, on a (size, size) grid.

    Args:
      gaze_uv: (..., 2) gaze (u, v) in *frame* pixel coordinates.
      size: heatmap resolution (HIR input resolution).
      frame_hw: (H, W) of the source frame, to normalise gaze coords.
      sigma_frac: Gaussian sigma as a fraction of the heatmap size.

    Returns:
      (..., size, size) float32 heatmap in [0, 1].
    """
    h, w = frame_hw
    gu = gaze_uv[..., 0] / w * size
    gv = gaze_uv[..., 1] / h * size
    rr = jnp.arange(size, dtype=jnp.float32)
    vv, uu = jnp.meshgrid(rr, rr, indexing="ij")
    sigma = sigma_frac * size
    d2 = (uu - gu[..., None, None]) ** 2 + (vv - gv[..., None, None]) ** 2
    return jnp.exp(-d2 / (2.0 * sigma**2))


def _conv(x, w, b, stride=1):
    # HIGHEST: a TPU's default would round the f32 operands through bf16
    # and move logits near the 0 decision threshold; on CPU it is a no-op.
    out = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out + b


def forward(params: Params, rgb64: Array, heat64: Array,
            patch_grid: int) -> Array:
    """Predict per-patch saliency logits.

    Args:
      params: HIR parameters.
      rgb64: (B, 64, 64, 3) downsampled frames.
      heat64: (B, 64, 64) gaze heatmaps.
      patch_grid: G — the frame is a GxG grid of patches.

    Returns:
      (B, G, G) saliency logits.
    """
    x = jnp.concatenate([rgb64, heat64[..., None]], axis=-1)
    x = jax.nn.relu(_conv(x, params["w1"], params["b1"], stride=2))  # 32
    x = jax.nn.relu(_conv(x, params["w2"], params["b2"], stride=2))  # 16
    x = _conv(x, params["w3"], params["b3"], stride=1)  # (B, 16, 16, 1)
    # Average-pool logits onto the patch grid.
    b, hh, ww, _ = x.shape
    assert hh % patch_grid == 0, (hh, patch_grid)
    k = hh // patch_grid
    x = x[..., 0].reshape(b, patch_grid, k, patch_grid, k)
    return x.mean(axis=(2, 4))


def binary_saliency(logits: Array) -> Array:
    """Binary saliency map S_t (paper: 'The output is a binary saliency map')."""
    return logits > 0.0


def loss_fn(params: Params, rgb64: Array, heat64: Array, labels: Array,
            patch_grid: int) -> Array:
    """BCE against ground-truth patch relevance labels (B, G, G) in {0,1}."""
    logits = forward(params, rgb64, heat64, patch_grid)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def n_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
