"""Synthetic egocentric world: analytic renderer + EVU task generator.

Real egocentric datasets (EgoEverything / HD-Epic / Nymeria) are not
available offline, so we build a procedural stand-in that provides *exact*
ground truth for every signal EPIC consumes:

  * RGB frames from a pinhole camera moving through a 3D scene
    (textured ground plane + K textured spheres = "objects"),
  * per-pixel metric depth (for depth-model training and for validating the
    reprojection geometry end-to-end),
  * camera pose per frame (the IMU signal),
  * gaze location per frame (fixation schedule over objects),
  * per-pixel object ids (for HIR relevance labels and EVU answers).

The EVU task mirrors the paper's multiple-choice setup: "which object was
the user attending during segment s?" — answerable only if patches covering
that object at that time survived compression.

Everything is pure JAX (jit/vmap-able); rendering is analytic ray casting
with unnormalised rays (z=1 in camera frame) so the ray parameter *is* the
camera-frame depth.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import geometry as geo

Array = jax.Array

_PALETTE = jnp.array(
    [
        [0.90, 0.20, 0.20],
        [0.20, 0.75, 0.25],
        [0.25, 0.35, 0.95],
        [0.95, 0.80, 0.20],
        [0.80, 0.25, 0.85],
        [0.20, 0.85, 0.85],
        [0.95, 0.55, 0.15],
        [0.55, 0.30, 0.10],
        [0.60, 0.85, 0.30],
        [0.35, 0.20, 0.75],
    ],
    dtype=jnp.float32,
)

PLANE_Y = 1.2  # ground plane height (+y is down)
# Pixels rendered in one batch.  The ray caster holds a few f32 values
# per pixel and object, so a sensor-size stream rendered in one vmap
# needs more memory than an accelerator has (30 frames at 1024x1024 asked
# a 16 GB TPU for 15.5 GB); longer or larger streams render in batches.
_RENDER_BATCH_PX = 1 << 22
SKY_DEPTH = 25.0


class Scene(NamedTuple):
    centers: Array  # (K, 3) sphere centres
    radii: Array  # (K,)
    colors: Array  # (K, 3)
    freqs: Array  # (K,) per-object texture frequency


class Stream(NamedTuple):
    """A rendered egocentric stream with full ground truth."""

    frames: Array  # (T, H, W, 3)
    depth: Array  # (T, H, W)
    obj_id: Array  # (T, H, W) int32; -1 sky, 0 plane, 1..K spheres
    poses: Array  # (T, 4, 4) camera-to-world
    gazes: Array  # (T, 2) pixel (u, v)
    gaze_target: Array  # (T,) int32 attended object (1..K)
    segment_of_frame: Array  # (T,) int32 fixation segment index


def make_scene(key: Array, n_obj: int = 6) -> Scene:
    # objects sized to subtend ~a patch on a 64px frame (f*r/z >~ 8px):
    # real egocentric footage has hand/counter-scale objects, not specks
    k1, k2, k3 = jax.random.split(key, 3)
    # spread in depth and azimuth to limit mutual occlusion
    x = (jnp.linspace(-3.2, 3.2, n_obj)
         + jax.random.uniform(k1, (n_obj,), minval=-0.4, maxval=0.4))
    z = jax.random.uniform(k2, (n_obj,), minval=2.6, maxval=6.5)
    radii = jax.random.uniform(k3, (n_obj,), minval=0.55, maxval=0.85)
    y = PLANE_Y - radii  # resting on the ground plane
    centers = jnp.stack([x, y, z], axis=-1)
    colors = _PALETTE[jnp.arange(n_obj) % _PALETTE.shape[0]]
    freqs = 4.0 + 3.0 * (jnp.arange(n_obj) % 3).astype(jnp.float32)
    return Scene(centers, radii, colors, freqs)


def look_at_pose(eye: Array, target: Array) -> Array:
    """Camera-to-world pose looking from ``eye`` toward ``target``.

    Convention: camera +x right, +y down, +z forward; world down is +y.
    """
    fwd = target - eye
    fwd = fwd / (jnp.linalg.norm(fwd) + 1e-8)
    down_w = jnp.array([0.0, 1.0, 0.0])
    right = jnp.cross(down_w, fwd)
    right = right / (jnp.linalg.norm(right) + 1e-8)
    down = jnp.cross(fwd, right)
    rot = jnp.stack([right, down, fwd], axis=-1)  # columns = camera axes
    return geo.pose_from_rt(rot, eye)


def render_frame(
    scene: Scene, pose: Array, intr: geo.Intrinsics, hw: Tuple[int, int]
) -> Tuple[Array, Array, Array]:
    """Ray-cast one frame.

    Returns:
      rgb: (H, W, 3); depth: (H, W) camera-frame z; obj_id: (H, W) int32.
    """
    h, w = hw
    uu, vv = jnp.meshgrid(
        jnp.arange(w, dtype=jnp.float32), jnp.arange(h, dtype=jnp.float32),
        indexing="xy",
    )
    # Unnormalised camera-frame ray dirs with z=1 -> ray param == depth.
    dirs_cam = jnp.stack(
        [(uu - intr.cx) / intr.f, (vv - intr.cy) / intr.f, jnp.ones_like(uu)],
        axis=-1,
    )  # (H, W, 3)
    rot = pose[:3, :3]
    eye = pose[:3, 3]
    dirs = jnp.einsum("ij,hwj->hwi", rot, dirs_cam)

    big = 1e6
    # Ground plane y = PLANE_Y.
    dy = dirs[..., 1]
    t_plane = (PLANE_Y - eye[1]) / jnp.where(jnp.abs(dy) > 1e-6, dy, 1e-6)
    t_plane = jnp.where(t_plane > 1e-3, t_plane, big)

    # Spheres.
    oc = eye[None, :] - scene.centers  # (K, 3)
    b = jnp.einsum("hwi,ki->hwk", dirs, oc)  # (H, W, K)
    a = jnp.sum(dirs * dirs, axis=-1)[..., None]  # (H, W, 1)
    c = jnp.sum(oc * oc, axis=-1)[None, None, :] - scene.radii[None, None, :] ** 2
    disc = b * b - a * c
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    t_sph = (-b - sq) / a
    t_sph = jnp.where((disc > 0) & (t_sph > 1e-3), t_sph, big)

    t_all = jnp.concatenate([t_plane[..., None], t_sph], axis=-1)  # (H,W,1+K)
    hit = jnp.argmin(t_all, axis=-1)  # 0 plane, 1..K spheres
    t_hit = jnp.min(t_all, axis=-1)
    is_sky = t_hit >= big * 0.5
    depth = jnp.where(is_sky, SKY_DEPTH, t_hit)
    obj_id = jnp.where(is_sky, -1, hit).astype(jnp.int32)

    # Shading: plane checker + per-object striped texture + lambert-ish term.
    point = eye[None, None, :] + t_hit[..., None] * dirs
    checker = (
        jnp.mod(jnp.floor(point[..., 0]) + jnp.floor(point[..., 2]), 2.0)
    )
    plane_rgb = (0.35 + 0.25 * checker)[..., None] * jnp.array([1.0, 0.95, 0.85])

    k_idx = jnp.clip(hit - 1, 0, scene.centers.shape[0] - 1)
    base = scene.colors[k_idx]  # (H, W, 3)
    local = point - scene.centers[k_idx]
    stripes = 0.75 + 0.25 * jnp.sin(
        scene.freqs[k_idx] * (local[..., 0] + 2.0 * local[..., 1])
    )
    normal = local / (jnp.linalg.norm(local, axis=-1, keepdims=True) + 1e-8)
    light = jnp.array([0.4, -0.8, -0.45])
    light = light / jnp.linalg.norm(light)
    lambert = 0.55 + 0.45 * jnp.clip(
        jnp.einsum("hwi,i->hw", normal, -light), 0.0, 1.0
    )
    sphere_rgb = base * (stripes * lambert)[..., None]

    sky_rgb = jnp.array([0.55, 0.70, 0.90])
    rgb = jnp.where(
        (obj_id == 0)[..., None],
        plane_rgb,
        jnp.where((obj_id > 0)[..., None], sphere_rgb, sky_rgb),
    )
    return jnp.clip(rgb, 0.0, 1.0), depth, obj_id


class StreamConfig(NamedTuple):
    n_frames: int = 60
    hw: Tuple[int, int] = (128, 128)
    n_obj: int = 6
    n_segments: int = 4  # fixation segments
    motion_amp: float = 0.8  # lateral head translation amplitude
    motion_freq: float = 0.05  # cycles per frame
    walk_speed: float = 0.02  # forward drift per frame (0 = standing)
    jitter: float = 0.01  # pose jitter (radians / metres)
    gaze_jitter_px: float = 2.0
    focal_frac: float = 0.8

    def intrinsics(self) -> geo.Intrinsics:
        h, w = self.hw
        return geo.Intrinsics.create(self.focal_frac * w, w / 2.0, h / 2.0)


def generate_stream(key: Array, cfg: StreamConfig) -> Tuple[Stream, Scene]:
    """Render a full egocentric stream with a fixation schedule."""
    k_scene, k_fix, k_jit, k_gaze = jax.random.split(key, 4)
    scene = make_scene(k_scene, cfg.n_obj)
    intr = cfg.intrinsics()
    t_axis = jnp.arange(cfg.n_frames, dtype=jnp.float32)

    # Fixation schedule: each segment attends one object (1..K).
    seg_len = cfg.n_frames // cfg.n_segments
    seg_targets = 1 + jax.random.randint(
        k_fix, (cfg.n_segments,), 0, cfg.n_obj
    )
    seg_of_frame = jnp.clip(
        (t_axis / seg_len).astype(jnp.int32), 0, cfg.n_segments - 1
    )
    gaze_target = seg_targets[seg_of_frame]  # (T,)

    # Head trajectory: slow lateral sway + drift toward the attended object.
    sway = cfg.motion_amp * jnp.sin(2 * jnp.pi * cfg.motion_freq * t_axis)
    eye = jnp.stack(
        [
            sway,
            jnp.full_like(t_axis, 0.0),
            -0.5 + cfg.walk_speed * t_axis,  # slow forward walk
        ],
        axis=-1,
    )
    eye = eye + cfg.jitter * jax.random.normal(k_jit, eye.shape)

    target_pts = scene.centers[gaze_target - 1]  # (T, 3)
    # Head points between straight-ahead and the attended object.
    ahead = eye + jnp.array([0.0, 0.3, 5.0])
    look = 0.5 * ahead + 0.5 * target_pts
    poses = jax.vmap(look_at_pose)(eye, look)

    def render_and_gaze(pose, tgt_pt, kg):
        rgb, depth, obj = render_frame(scene, pose, intr, cfg.hw)
        cam_pt = geo.transform_points(geo.invert_pose(pose), tgt_pt)
        uv, _, _ = geo.project(cam_pt, intr)
        uv = uv + cfg.gaze_jitter_px * jax.random.normal(kg, (2,))
        h, w = cfg.hw
        uv = jnp.clip(uv, 1.0, jnp.array([w - 2.0, h - 2.0]))
        return rgb, depth, obj, uv

    gaze_keys = jax.random.split(k_gaze, cfg.n_frames)
    frames, depth, obj_id, gazes = jax.lax.map(
        lambda x: render_and_gaze(*x),
        (poses, target_pts, gaze_keys),
        batch_size=max(1, _RENDER_BATCH_PX // (cfg.hw[0] * cfg.hw[1])),
    )
    return (
        Stream(frames, depth, obj_id, poses, gazes, gaze_target, seg_of_frame),
        scene,
    )


# ---------------------------------------------------------------------------
# Labels derived from ground truth.
# ---------------------------------------------------------------------------


def patch_relevance_labels(
    obj_id: Array, gaze_target: Array, patch: int
) -> Array:
    """HIR training labels: a patch is relevant iff it contains pixels of the
    currently-attended object.

    Args:
      obj_id: (T, H, W) int32; gaze_target: (T,) int32.

    Returns:
      (T, G, G) float32 in {0, 1}.
    """
    t, h, w = obj_id.shape
    g = h // patch
    m = (obj_id == gaze_target[:, None, None]).astype(jnp.float32)
    m = m[:, : g * patch, : g * patch]
    m = m.reshape(t, g, patch, g, patch)
    return (m.mean(axis=(2, 4)) > 0.02).astype(jnp.float32)


def depth_training_batch(
    key: Array, cfg: StreamConfig, batch: int
) -> Tuple[Array, Array]:
    """Random rendered views resized to 64x64 for depth-model training."""
    from repro.core import depth as depth_mod

    stream, _ = generate_stream(key, cfg._replace(n_frames=batch))
    rgb64 = depth_mod.resize_image(stream.frames, 64)
    d = stream.depth[:, None]  # (B, 1, H, W) -> resize as image
    d64 = jax.image.resize(
        stream.depth[..., None], (batch, 64, 64, 1), method="bilinear"
    )[..., 0]
    del d
    return rgb64, d64
