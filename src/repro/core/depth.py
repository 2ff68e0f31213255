"""Depth Estimation Module (EPIC paper, Section 3.2).

A FastDepth-style lightweight monocular depth CNN:

* input resized to 64x64 (paper: "we resize the input image to 64x64 and
  interpolate the predicted depth map back to the original resolution"),
* MobileNet-ish depthwise-separable encoder, nearest-upsample decoder with
  additive skip connections,
* int8 post-training quantization path (paper: "we also quantize the model to
  8-bit integers").

The network is deliberately tiny (~0.2M params): on the EPIC accelerator it
runs on a 16x16 systolic array; on TPU its convolutions lower to MXU matmuls
(the int8 path additionally has a Pallas int8 matmul kernel under
``repro.kernels.int8_matmul`` exercised through :func:`im2col`).

Parameters are plain pytrees (dicts); no framework dependency.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Dict[str, Dict[str, Array]]

DEPTH_INPUT = 64  # paper: inputs resized to 64x64

# (name, kind, c_in, c_out, stride); kind: 'conv' 3x3, 'dw' depthwise+pointwise
_ENCODER = (
    ("enc0", "conv", 3, 16, 2),  # 64 -> 32
    ("enc1", "dw", 16, 32, 2),  # 32 -> 16
    ("enc2", "dw", 32, 64, 2),  # 16 -> 8
    ("enc3", "dw", 64, 64, 1),  # 8 -> 8
)
_DECODER = (
    ("dec0", "dw", 64, 32, 1),  # up 8 -> 16, skip enc1 out
    ("dec1", "dw", 32, 16, 1),  # up 16 -> 32, skip enc0 out
    ("dec2", "dw", 16, 16, 1),  # up 32 -> 64
)
_HEAD = ("head", "conv", 16, 1, 1)


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def init_params(key: Array) -> Params:
    """Initialise FastDepth-lite parameters."""
    params: Params = {}
    layers = _ENCODER + _DECODER + (_HEAD,)
    keys = jax.random.split(key, len(layers) * 2)
    ki = 0
    for name, kind, cin, cout, _ in layers:
        if kind == "conv":
            params[name] = {
                "w": _conv_init(keys[ki], 3, 3, cin, cout),
                "b": jnp.zeros((cout,), jnp.float32),
            }
            ki += 2
        else:  # depthwise separable: 3x3 depthwise + 1x1 pointwise
            params[name] = {
                "dw": _conv_init(keys[ki], 3, 3, 1, cin).reshape(3, 3, 1, cin),
                "pw": _conv_init(keys[ki + 1], 1, 1, cin, cout),
                "b": jnp.zeros((cout,), jnp.float32),
            }
            ki += 2
    return params


def n_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def _conv2d(x: Array, w: Array, stride: int = 1, groups: int = 1) -> Array:
    """NHWC conv with SAME padding, at f32 precision on every platform
    (a TPU's default would round the operands through bf16)."""
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST,
    )


def _block(x: Array, p: Dict[str, Array], kind: str, stride: int) -> Array:
    if kind == "conv":
        x = _conv2d(x, p["w"], stride) + p["b"]
    else:
        cin = x.shape[-1]
        x = _conv2d(x, p["dw"], stride, groups=cin)
        x = _conv2d(x, p["pw"], 1) + p["b"]
    return jax.nn.relu(x)


def _upsample2(x: Array) -> Array:
    """Nearest-neighbour 2x upsample (NHWC)."""
    n, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (n, h, 2, w, 2, c))
    return x.reshape(n, h * 2, w * 2, c)


def forward(params: Params, rgb64: Array) -> Array:
    """Predict depth from a 64x64 RGB image batch.

    Args:
      params: model parameters.
      rgb64: (B, 64, 64, 3) float32 in [0, 1].

    Returns:
      (B, 64, 64) positive depth (softplus-activated).
    """
    x = rgb64
    skips = {}
    for name, kind, _, _, stride in _ENCODER:
        x = _block(x, params[name], kind, stride)
        skips[name] = x
    for i, (name, kind, _, _, stride) in enumerate(_DECODER):
        x = _upsample2(x)
        x = _block(x, params[name], kind, stride)
        skip_name = ("enc1", "enc0", None)[i]
        if skip_name is not None:
            x = x + skips[skip_name]
    x = _conv2d(x, params["head"]["w"], 1) + params["head"]["b"]
    return jax.nn.softplus(x[..., 0]) + 0.05  # strictly positive depth


def resize_image(img: Array, size: int) -> Array:
    """Bilinear resize (H, W, C) or (B, H, W, C) to (size, size)."""
    batched = img.ndim == 4
    if not batched:
        img = img[None]
    out = jax.image.resize(
        img, (img.shape[0], size, size, img.shape[-1]), method="bilinear"
    )
    return out if batched else out[0]


def predict_fullres(params: Params, frame: Array) -> Array:
    """Paper inference path: resize frame -> 64x64 -> CNN -> upsample back.

    Args:
      frame: (H, W, 3) float32.

    Returns:
      (H, W) depth at the original resolution.
    """
    h, w = frame.shape[0], frame.shape[1]
    small = resize_image(frame, DEPTH_INPUT)[None]
    if isinstance(params, QuantizedParams):  # int8 deployment path (§3.2)
        d = forward_int8(params, small)[0]
    else:
        d = forward(params, small)[0]  # (64, 64)
    return jax.image.resize(d, (h, w), method="bilinear")


def loss_fn(params: Params, rgb64: Array, depth64: Array) -> Array:
    """Scale-aware log-depth L2 loss for training on synthetic ground truth."""
    pred = forward(params, rgb64)
    return jnp.mean((jnp.log(pred) - jnp.log(depth64 + 1e-6)) ** 2)


# ---------------------------------------------------------------------------
# Int8 post-training quantization (paper Section 3.2).
# ---------------------------------------------------------------------------


class QuantizedParams(NamedTuple):
    """Symmetric per-output-channel int8 weights + float biases/scales."""

    qweights: Params  # same tree, int8 weight leaves
    scales: Params  # per-out-channel float scales
    act_scale: Dict[str, Array]  # per-layer activation scale (per-tensor)


def quantize_weight(w: Array) -> Tuple[Array, Array]:
    """Per-output-channel symmetric int8 quantization (last axis = out ch)."""
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_params(params: Params, calib_rgb64: Array) -> QuantizedParams:
    """Post-training quantization with activation calibration.

    Activation scales are calibrated as the max-abs of each layer's input
    over a calibration batch (paper fine-tunes on held-out splits; we
    calibrate on synthetic frames).
    """
    qweights: Params = {}
    scales: Params = {}
    for name, layer in params.items():
        qweights[name] = {}
        scales[name] = {}
        for k, v in layer.items():
            if k == "b":
                qweights[name][k] = v
                scales[name][k] = jnp.ones((), jnp.float32)
            else:
                q, s = quantize_weight(v)
                qweights[name][k] = q
                scales[name][k] = s
    act_scale = _calibrate(params, calib_rgb64)
    return QuantizedParams(qweights, scales, act_scale)


def _calibrate(params: Params, rgb64: Array) -> Dict[str, Array]:
    """Record per-layer input max-abs on a calibration batch."""
    record: Dict[str, Array] = {}
    x = rgb64
    skips = {}
    for name, kind, _, _, stride in _ENCODER:
        record[name] = jnp.max(jnp.abs(x))
        x = _block(x, params[name], kind, stride)
        skips[name] = x
    for i, (name, kind, _, _, stride) in enumerate(_DECODER):
        x = _upsample2(x)
        record[name] = jnp.max(jnp.abs(x))
        x = _block(x, params[name], kind, stride)
        skip_name = ("enc1", "enc0", None)[i]
        if skip_name is not None:
            x = x + skips[skip_name]
    record["head"] = jnp.max(jnp.abs(x))
    return record


def _qconv(x: Array, qw: Array, wscale: Array, xscale: Array,
           stride: int = 1, groups: int = 1) -> Array:
    """Int8-simulated conv: quantize input, integer conv, dequantize.

    The arithmetic matches an int8 MAC array (int8 x int8 -> int32
    accumulate): inputs and weights are true int8 values; the conv runs in
    int32 precision and is dequantized with the product of scales. On TPU the
    same computation maps to the Pallas ``int8_matmul`` kernel via im2col
    (see ``repro/kernels/int8_matmul``).
    """
    sx = jnp.maximum(xscale, 1e-8) / 127.0
    qx = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    out = jax.lax.conv_general_dilated(
        qx.astype(jnp.int32),
        qw.astype(jnp.int32),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    # wscale has shape (1,1,1,cout) (or (1,)*n) -> broadcast over NHWC out.
    return out.astype(jnp.float32) * sx * wscale.reshape(1, 1, 1, -1)


def _qblock(x, qp, sp, xscale, kind, stride):
    if kind == "conv":
        x = _qconv(x, qp["w"], sp["w"], xscale, stride) + qp["b"]
    else:
        cin = x.shape[-1]
        x = _qconv(x, qp["dw"], sp["dw"], xscale, stride, groups=cin)
        x = _qconv(x, qp["pw"], sp["pw"], jnp.max(jnp.abs(x)), 1) + qp["b"]
    return jax.nn.relu(x)


def forward_int8(q: QuantizedParams, rgb64: Array) -> Array:
    """Int8 inference path mirroring :func:`forward`."""
    x = rgb64
    skips = {}
    for name, kind, _, _, stride in _ENCODER:
        x = _qblock(x, q.qweights[name], q.scales[name], q.act_scale[name],
                    kind, stride)
        skips[name] = x
    for i, (name, kind, _, _, stride) in enumerate(_DECODER):
        x = _upsample2(x)
        x = _qblock(x, q.qweights[name], q.scales[name], q.act_scale[name],
                    kind, stride)
        skip_name = ("enc1", "enc0", None)[i]
        if skip_name is not None:
            x = x + skips[skip_name]
    x = (
        _qconv(x, q.qweights["head"]["w"], q.scales["head"]["w"],
               q.act_scale["head"], 1)
        + q.qweights["head"]["b"]
    )
    return jax.nn.softplus(x[..., 0]) + 0.05


def memory_bytes(params: Params, int8: bool) -> int:
    """Model weight footprint (paper: int8 cuts depth-module memory 4x)."""
    per = 1 if int8 else 4
    return sum(int(x.size) * per for x in jax.tree.leaves(params))
