"""The camera: a seeded synthetic egocentric clip bank, rendered on the
device in one jitted call.

A copy of the analytic ray caster of ``src/repro/data/synthetic.py``
(textured ground plane and spheres, a walking or fixating head, a gaze
track), kept here so that the traffic cannot change under a later change
to the program.  Frames leave the renderer as 8-bit sensor levels
stored in float32 (``round(255 x) / 255``) and depth as whole
millimetres stored in float32 (``float32(mm) / 1000``).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PLANE_Y = 1.2  # ground plane height (+y is down)
SKY_DEPTH = 25.0
RENDER_BATCH_PX = 1 << 22  # pixels ray-cast at once
HIGHEST = jax.lax.Precision.HIGHEST

PALETTE = np.array(
    [
        [0.90, 0.20, 0.20], [0.20, 0.75, 0.25], [0.25, 0.35, 0.95],
        [0.95, 0.80, 0.20], [0.80, 0.25, 0.85], [0.20, 0.85, 0.85],
        [0.95, 0.55, 0.15], [0.55, 0.30, 0.10], [0.60, 0.85, 0.30],
        [0.35, 0.20, 0.75],
    ],
    np.float32,
)


class Motion(NamedTuple):
    """Head and gaze dynamics of one traffic mix."""

    n_obj: int = 6
    n_segments: int = 4
    motion_amp: float = 0.8
    motion_freq: float = 0.05
    walk_speed: float = 0.02
    jitter: float = 0.01
    gaze_jitter_px: float = 2.0


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _pose(rot, trans):
    top = jnp.concatenate([rot, trans[:, None]], axis=-1)
    return jnp.concatenate([top, jnp.array([[0.0, 0.0, 0.0, 1.0]])], axis=0)


def _look_at(eye, target):
    fwd = target - eye
    fwd = fwd / (jnp.linalg.norm(fwd) + 1e-8)
    right = jnp.cross(jnp.array([0.0, 1.0, 0.0]), fwd)
    right = right / (jnp.linalg.norm(right) + 1e-8)
    down = jnp.cross(fwd, right)
    return _pose(jnp.stack([right, down, fwd], axis=-1), eye)


def _scene(key, n_obj):
    k1, k2, k3 = jax.random.split(key, 3)
    x = jnp.linspace(-3.2, 3.2, n_obj) + jax.random.uniform(
        k1, (n_obj,), minval=-0.4, maxval=0.4
    )
    z = jax.random.uniform(k2, (n_obj,), minval=2.6, maxval=6.5)
    radii = jax.random.uniform(k3, (n_obj,), minval=0.55, maxval=0.85)
    centers = jnp.stack([x, PLANE_Y - radii, z], axis=-1)
    colors = jnp.asarray(PALETTE)[jnp.arange(n_obj) % PALETTE.shape[0]]
    freqs = 4.0 + 3.0 * (jnp.arange(n_obj) % 3).astype(jnp.float32)
    return centers, radii, colors, freqs


def _render(scene, pose, f, hw):
    centers, radii, colors, freqs = scene
    h, w = hw
    uu, vv = jnp.meshgrid(
        jnp.arange(w, dtype=jnp.float32), jnp.arange(h, dtype=jnp.float32),
        indexing="xy",
    )
    dirs_cam = jnp.stack(
        [(uu - w / 2.0) / f, (vv - h / 2.0) / f, jnp.ones_like(uu)], axis=-1
    )
    eye = pose[:3, 3]
    dirs = jnp.einsum("ij,hwj->hwi", pose[:3, :3], dirs_cam)
    big = 1e6
    dy = dirs[..., 1]
    t_plane = (PLANE_Y - eye[1]) / jnp.where(jnp.abs(dy) > 1e-6, dy, 1e-6)
    t_plane = jnp.where(t_plane > 1e-3, t_plane, big)
    oc = eye[None, :] - centers
    b = jnp.einsum("hwi,ki->hwk", dirs, oc)
    a = jnp.sum(dirs * dirs, axis=-1)[..., None]
    c = jnp.sum(oc * oc, axis=-1)[None, None, :] - radii[None, None, :] ** 2
    disc = b * b - a * c
    t_sph = (-b - jnp.sqrt(jnp.maximum(disc, 0.0))) / a
    t_sph = jnp.where((disc > 0) & (t_sph > 1e-3), t_sph, big)
    t_all = jnp.concatenate([t_plane[..., None], t_sph], axis=-1)
    hit = jnp.argmin(t_all, axis=-1)
    t_hit = jnp.min(t_all, axis=-1)
    sky = t_hit >= big * 0.5
    depth = jnp.where(sky, SKY_DEPTH, t_hit)
    obj = jnp.where(sky, -1, hit)
    point = eye[None, None, :] + t_hit[..., None] * dirs
    checker = jnp.mod(jnp.floor(point[..., 0]) + jnp.floor(point[..., 2]), 2.0)
    plane_rgb = (0.35 + 0.25 * checker)[..., None] * jnp.array([1.0, 0.95, 0.85])
    k = jnp.clip(hit - 1, 0, centers.shape[0] - 1)
    local = point - centers[k]
    stripes = 0.75 + 0.25 * jnp.sin(freqs[k] * (local[..., 0] + 2.0 * local[..., 1]))
    normal = local / (jnp.linalg.norm(local, axis=-1, keepdims=True) + 1e-8)
    light = jnp.array([0.4, -0.8, -0.45])
    light = light / jnp.linalg.norm(light)
    lambert = 0.55 + 0.45 * jnp.clip(jnp.einsum("hwi,i->hw", normal, -light), 0.0, 1.0)
    sphere_rgb = colors[k] * (stripes * lambert)[..., None]
    rgb = jnp.where(
        (obj == 0)[..., None], plane_rgb,
        jnp.where((obj > 0)[..., None], sphere_rgb, jnp.array([0.55, 0.70, 0.90])),
    )
    return jnp.clip(rgb, 0.0, 1.0), depth


def _clip(key, m: Motion, n_frames: int, hw: Tuple[int, int], focal_frac: float):
    k_scene, k_fix, k_jit, k_gaze = jax.random.split(key, 4)
    scene = _scene(k_scene, m.n_obj)
    f = focal_frac * hw[1]
    t = jnp.arange(n_frames, dtype=jnp.float32)
    seg_len = max(1, n_frames // m.n_segments)
    targets = 1 + jax.random.randint(k_fix, (m.n_segments,), 0, m.n_obj)
    seg = jnp.clip((t / seg_len).astype(jnp.int32), 0, m.n_segments - 1)
    target_pts = scene[0][targets[seg] - 1]
    sway = m.motion_amp * jnp.sin(2 * jnp.pi * m.motion_freq * t)
    eye = jnp.stack([sway, jnp.zeros_like(t), -0.5 + m.walk_speed * t], axis=-1)
    eye = eye + m.jitter * jax.random.normal(k_jit, eye.shape)
    look = 0.5 * (eye + jnp.array([0.0, 0.3, 5.0])) + 0.5 * target_pts
    poses = jax.vmap(_look_at)(eye, look)
    h, w = hw

    def one(x):
        pose, tgt, kg = x
        rgb, depth = _render(scene, pose, f, hw)
        rot_t = pose[:3, :3].T
        cam = jnp.einsum("ij,j->i", rot_t, tgt - pose[:3, 3], precision=HIGHEST)
        z = jnp.where(cam[2] > 1e-6, cam[2], 1.0)
        uv = jnp.stack([cam[0] / z * f + w / 2.0, cam[1] / z * f + h / 2.0])
        uv = uv + m.gaze_jitter_px * jax.random.normal(kg, (2,))
        uv = jnp.clip(uv, 1.0, jnp.array([w - 2.0, h - 2.0]))
        return (
            jnp.round(rgb * 255.0) / 255.0,
            jnp.round(depth * 1000.0) / 1000.0,
            uv,
        )

    frames, depth, gazes = jax.lax.map(
        one, (poses, target_pts, jax.random.split(k_gaze, n_frames)),
        batch_size=max(1, RENDER_BATCH_PX // (h * w)),
    )
    return frames, poses, gazes, depth


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _render_bank(key, m, clips, n_frames, hw, focal_frac):
    keys = jax.random.split(key, clips)
    return jax.lax.map(lambda k: _clip(k, m, n_frames, hw, focal_frac), keys)


def render_bank(
    seed: int, motion: Dict, clips: int, clip_frames: int, frame: int,
    focal_frac: float,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Render ``clips`` clips of ``clip_frames`` square frames on the
    default device in one call: ``(frames, poses, gazes, depth)`` with
    leading axes ``(clip, frame)``, left on the device."""
    return _render_bank(
        root_key(seed), Motion(**motion), clips, clip_frames, (frame, frame),
        focal_frac,
    )
