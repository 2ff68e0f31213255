"""Geometry-based frame/patch reprojection (EPIC paper, Section 3.1, Eq. 1).

Conventions
-----------
* Pixel coordinates ``(u, v)``: ``u`` along width (column), ``v`` along height
  (row). Origin at the top-left pixel centre.
* Camera frame (OpenCV): ``+x`` right, ``+y`` down, ``+z`` forward (optical
  axis). ``depth`` is the ``z`` coordinate in the camera frame.
* Intrinsics ``K = [[f, 0, cx], [0, f, cy], [0, 0, 1]]``.
* A *pose* ``U`` is the camera-to-world rigid transform ``T_wc`` as a 4x4
  matrix: ``x_world = R @ x_cam + t``.

The paper expresses reprojection (Eq. 1) as a chain of 4x4 matrices acting on
the homogeneous vector ``[u, v, f, 1]``:

    [o'_f2, f, 1]^T = T_wc(f) . T_{p1->p2} . T_cw(f, d1) . [o'_f1, f, 1]^T

``eq1_reproject`` implements that literal chain; ``reproject_points``
implements the equivalent (and cheaper) lift -> rigid transform -> project
pipeline. A property test asserts the two agree.

All functions are shape-polymorphic over leading point dimensions and are
vmap/jit friendly (pure, no Python branching on traced values).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-6
# The pose chain is a handful of 4x4 / 3x3 products: full f32 precision
# costs nothing, while the TPU's default matmul precision (one bf16 pass)
# would move warped pixels by whole pixels at sensor resolution.
_HIGHEST = jax.lax.Precision.HIGHEST


def _matmul(a: Array, b: Array) -> Array:
    return jnp.matmul(a, b, precision=_HIGHEST)


def _matvec(m: Array, v: Array) -> Array:
    return jnp.einsum("...ij,...j->...i", m, v, precision=_HIGHEST)


class Intrinsics(NamedTuple):
    """Pinhole camera intrinsics (square pixels, as in the paper)."""

    f: Array  # scalar focal length in pixels
    cx: Array  # principal point x (pixels)
    cy: Array  # principal point y (pixels)

    @staticmethod
    def create(f: float, cx: float, cy: float) -> "Intrinsics":
        return Intrinsics(jnp.float32(f), jnp.float32(cx), jnp.float32(cy))

    def matrix(self) -> Array:
        """3x3 K matrix."""
        z = jnp.zeros_like(self.f)
        o = jnp.ones_like(self.f)
        return jnp.stack(
            [
                jnp.stack([self.f, z, self.cx]),
                jnp.stack([z, self.f, self.cy]),
                jnp.stack([z, z, o]),
            ]
        )


def pose_from_rt(rot: Array, trans: Array) -> Array:
    """Build a 4x4 camera-to-world pose from a 3x3 rotation and translation.

    Args:
      rot: (..., 3, 3) rotation matrix.
      trans: (..., 3) translation.

    Returns:
      (..., 4, 4) homogeneous transform.
    """
    batch = jnp.broadcast_shapes(rot.shape[:-2], trans.shape[:-1])
    rot = jnp.broadcast_to(rot, batch + (3, 3))
    trans = jnp.broadcast_to(trans, batch + (3,))
    top = jnp.concatenate([rot, trans[..., :, None]], axis=-1)  # (...,3,4)
    bottom = jnp.broadcast_to(
        jnp.array([0.0, 0.0, 0.0, 1.0], dtype=rot.dtype), batch + (1, 4)
    )
    return jnp.concatenate([top, bottom], axis=-2)


def rotation_xyz(angles: Array) -> Array:
    """Rotation matrix from XYZ Euler angles (radians). angles: (..., 3)."""
    ax, ay, az = angles[..., 0], angles[..., 1], angles[..., 2]
    cx_, sx = jnp.cos(ax), jnp.sin(ax)
    cy_, sy = jnp.cos(ay), jnp.sin(ay)
    cz, sz = jnp.cos(az), jnp.sin(az)
    o = jnp.ones_like(ax)
    z = jnp.zeros_like(ax)
    rx = jnp.stack(
        [
            jnp.stack([o, z, z], -1),
            jnp.stack([z, cx_, -sx], -1),
            jnp.stack([z, sx, cx_], -1),
        ],
        -2,
    )
    ry = jnp.stack(
        [
            jnp.stack([cy_, z, sy], -1),
            jnp.stack([z, o, z], -1),
            jnp.stack([-sy, z, cy_], -1),
        ],
        -2,
    )
    rz = jnp.stack(
        [
            jnp.stack([cz, -sz, z], -1),
            jnp.stack([sz, cz, z], -1),
            jnp.stack([z, z, o], -1),
        ],
        -2,
    )
    return _matmul(_matmul(rz, ry), rx)


def invert_pose(pose: Array) -> Array:
    """Invert a rigid 4x4 transform analytically (R^T, -R^T t)."""
    rot = pose[..., :3, :3]
    trans = pose[..., :3, 3]
    rot_t = jnp.swapaxes(rot, -1, -2)
    new_t = -_matvec(rot_t, trans)
    return pose_from_rt(rot_t, new_t)


def relative_transform(src_pose: Array, dst_pose: Array) -> Array:
    """T_{p1->p2}: maps points in the *src* camera frame to the *dst* frame.

    Both poses are camera-to-world; the relative transform is
    ``inv(T_wc_dst) @ T_wc_src``.
    """
    return _matmul(invert_pose(dst_pose), src_pose)


def lift(uv: Array, depth: Array, intr: Intrinsics) -> Array:
    """Lift pixel coordinates + depth to 3D camera-frame points.

    Args:
      uv: (..., 2) pixel coordinates (u, v).
      depth: (...,) positive z-depth.
      intr: camera intrinsics.

    Returns:
      (..., 3) camera-frame points.
    """
    x = (uv[..., 0] - intr.cx) / intr.f * depth
    y = (uv[..., 1] - intr.cy) / intr.f * depth
    return jnp.stack([x, y, depth], axis=-1)


def project(xyz: Array, intr: Intrinsics) -> Tuple[Array, Array, Array]:
    """Project camera-frame 3D points to the image plane.

    Returns:
      uv: (..., 2) pixel coordinates.
      z:  (...,) depth in the destination camera frame.
      valid: (...,) bool — point is in front of the camera.
    """
    z = xyz[..., 2]
    valid = z > _EPS
    safe_z = jnp.where(valid, z, 1.0)
    u = xyz[..., 0] / safe_z * intr.f + intr.cx
    v = xyz[..., 1] / safe_z * intr.f + intr.cy
    return jnp.stack([u, v], axis=-1), z, valid


def transform_points(t4: Array, xyz: Array) -> Array:
    """Apply a 4x4 rigid transform to (..., 3) points."""
    return _matvec(t4[..., :3, :3], xyz) + t4[..., :3, 3]


def reproject_points(
    uv: Array, depth: Array, intr: Intrinsics, t_rel: Array
) -> Tuple[Array, Array, Array]:
    """Reproject pixels observed at pose P1 into the image plane at pose P2.

    This is the lift -> transform -> project pipeline equivalent to the
    paper's Eq. 1.

    Args:
      uv: (..., 2) source pixel coordinates.
      depth: (...,) source z-depth.
      intr: shared camera intrinsics.
      t_rel: (4, 4) transform from the source camera frame to the destination
        camera frame (see :func:`relative_transform`).

    Returns:
      uv2: (..., 2) destination pixel coordinates.
      z2:  (...,) destination depth.
      valid: (...,) bool.
    """
    xyz1 = lift(uv, depth, intr)
    xyz2 = transform_points(t_rel, xyz1)
    return project(xyz2, intr)


# ---------------------------------------------------------------------------
# Literal Eq. 1 formulation (paper-faithful 4x4 chain on [u, v, f, 1]).
# ---------------------------------------------------------------------------


def _t_cw(intr: Intrinsics, depth: Array) -> Array:
    """T_cw(f, d): homogeneous [u, v, f, 1] -> camera-frame [x, y, z, 1].

    x = d (u - cx) / f ; y = d (v - cy) / f ; z = d.
    Built per-point because d varies per point: (..., 4, 4).
    """
    d_over_f = depth / intr.f
    z = jnp.zeros_like(depth)
    o = jnp.ones_like(depth)
    rows = [
        jnp.stack([d_over_f, z, z, -d_over_f * intr.cx], -1),
        jnp.stack([z, d_over_f, z, -d_over_f * intr.cy], -1),
        jnp.stack([z, z, d_over_f, z], -1),
        jnp.stack([z, z, z, o], -1),
    ]
    return jnp.stack(rows, -2)


def _t_wc(intr: Intrinsics) -> Array:
    """T_wc(f): camera-frame [x, y, z, 1] -> homogeneous image [u*w, v*w, f*w, w].

    After dividing by the last coordinate: [f x/z + cx, f y/z + cy, f, 1].
    """
    f, cx, cy = intr.f, intr.cx, intr.cy
    z = jnp.zeros_like(f)
    o = jnp.ones_like(f)
    return jnp.stack(
        [
            jnp.stack([f, z, cx, z]),
            jnp.stack([z, f, cy, z]),
            jnp.stack([z, z, f, z]),
            jnp.stack([z, z, o, z]),
        ]
    )


def eq1_reproject(
    uv: Array, depth: Array, intr: Intrinsics, t_rel: Array
) -> Tuple[Array, Array, Array]:
    """Paper Eq. 1 as a literal chain of 4x4 matrices.

    ``[o'_f2, f, 1] = T_wc(f) T_{p1->p2} T_cw(f, d1) [o'_f1, f, 1]``

    Semantically identical to :func:`reproject_points`; kept as the
    faithfulness reference (property-tested for equality).
    """
    homog = jnp.stack(
        [
            uv[..., 0],
            uv[..., 1],
            jnp.broadcast_to(intr.f, uv[..., 0].shape),
            jnp.ones_like(uv[..., 0]),
        ],
        -1,
    )
    chain = _matmul(_matmul(_t_wc(intr), t_rel), _t_cw(intr, depth))
    out = _matvec(chain, homog)
    w = out[..., 3]
    valid = w > _EPS
    safe_w = jnp.where(valid, w, 1.0)
    uv2 = out[..., :2] / safe_w[..., None]
    z2 = w  # w == z in the destination camera frame
    return uv2, z2, valid


# ---------------------------------------------------------------------------
# Patch-level helpers: pixel grids, warps, bounding boxes.
# ---------------------------------------------------------------------------


def patch_pixel_grid(origin_yx: Array, patch: int) -> Array:
    """Pixel-centre coordinates (u, v) of a PxP patch.

    Args:
      origin_yx: (..., 2) top-left (row, col) of the patch in its frame.
      patch: patch side length P (static).

    Returns:
      (..., P, P, 2) of (u, v) coordinates.
    """
    rr = jnp.arange(patch, dtype=jnp.float32)
    vv, uu = jnp.meshgrid(rr, rr, indexing="ij")  # (P, P) row, col offsets
    u = origin_yx[..., 1][..., None, None] + uu
    v = origin_yx[..., 0][..., None, None] + vv
    return jnp.stack([u, v], axis=-1)


def warp_patch_coords(
    origin_yx: Array,
    depth_patch: Array,
    intr: Intrinsics,
    t_rel: Array,
    patch: int,
) -> Tuple[Array, Array]:
    """Warp a source patch's pixel grid into the destination view.

    Args:
      origin_yx: (2,) patch top-left (row, col) in the source frame.
      depth_patch: (P, P) per-pixel source depth.
      intr: intrinsics.
      t_rel: (4, 4) source->destination camera transform.
      patch: P.

    Returns:
      coords: (P, P, 2) destination (u, v) coordinates.
      valid:  (P, P) bool — destination z > 0.
    """
    grid = patch_pixel_grid(origin_yx, patch)  # (P, P, 2)
    uv2, _, valid = reproject_points(grid, depth_patch, intr, t_rel)
    return uv2, valid


def bilinear_sample(
    image: Array, coords: Array
) -> Tuple[Array, Array]:
    """Bilinearly sample ``image`` at float (u, v) coordinates.

    Args:
      image: (H, W, C).
      coords: (..., 2) of (u, v).

    Returns:
      values: (..., C) sampled values (0 where invalid).
      valid:  (...,) bool — all four corners inside the image.
    """
    h, w = image.shape[0], image.shape[1]
    u = coords[..., 0]
    v = coords[..., 1]
    u0 = jnp.floor(u)
    v0 = jnp.floor(v)
    du = u - u0
    dv = v - v0
    u0i = u0.astype(jnp.int32)
    v0i = v0.astype(jnp.int32)

    valid = (u0 >= 0) & (u0 + 1 <= w - 1) & (v0 >= 0) & (v0 + 1 <= h - 1)
    u0c = jnp.clip(u0i, 0, w - 2)
    v0c = jnp.clip(v0i, 0, h - 2)

    def gather(vi, ui):
        return image[vi, ui]  # advanced indexing -> XLA gather

    p00 = gather(v0c, u0c)
    p01 = gather(v0c, u0c + 1)
    p10 = gather(v0c + 1, u0c)
    p11 = gather(v0c + 1, u0c + 1)
    w00 = ((1 - du) * (1 - dv))[..., None]
    w01 = (du * (1 - dv))[..., None]
    w10 = ((1 - du) * dv)[..., None]
    w11 = (du * dv)[..., None]
    out = p00 * w00 + p01 * w01 + p10 * w10 + p11 * w11
    return jnp.where(valid[..., None], out, 0.0), valid


def reproject_bbox(
    origin_yx: Array,
    corner_depths: Array,
    intr: Intrinsics,
    t_rel: Array,
    patch: int,
) -> Tuple[Array, Array]:
    """Reproject only a patch's bounding box (EPIC accelerator, Section 4.1.1).

    The four patch corners are lifted with their depths and reprojected; the
    axis-aligned bounding box of the result is the candidate region in the
    destination view. This is the cheap prefilter the EPIC reprojection
    engine runs before any full pixel-level comparison.

    Args:
      origin_yx: (..., 2) patch top-left (row, col).
      corner_depths: (..., 4) depth at [tl, tr, bl, br] corners.
      intr: intrinsics.
      t_rel: (4, 4) or broadcastable (..., 4, 4).

    Returns:
      bbox: (..., 4) as (vmin, umin, vmax, umax) in destination pixels.
      valid: (...,) bool — all corners in front of the destination camera.
    """
    p = jnp.float32(patch - 1)
    zeros = jnp.zeros_like(origin_yx[..., 0])
    offs = jnp.stack(
        [
            jnp.stack([zeros, zeros], -1),
            jnp.stack([zeros, zeros + p], -1),
            jnp.stack([zeros + p, zeros], -1),
            jnp.stack([zeros + p, zeros + p], -1),
        ],
        axis=-2,
    )  # (..., 4, 2) row/col corner offsets
    corners_yx = origin_yx[..., None, :] + offs
    corners_uv = jnp.stack(
        [corners_yx[..., 1], corners_yx[..., 0]], axis=-1
    )  # (..., 4, 2)
    if t_rel.ndim > 2:
        t_rel = t_rel[..., None, :, :]
    uv2, _, valid = reproject_points(corners_uv, corner_depths, intr, t_rel)
    vmin = jnp.min(uv2[..., 1], axis=-1)
    vmax = jnp.max(uv2[..., 1], axis=-1)
    umin = jnp.min(uv2[..., 0], axis=-1)
    umax = jnp.max(uv2[..., 0], axis=-1)
    bbox = jnp.stack([vmin, umin, vmax, umax], axis=-1)
    return bbox, jnp.all(valid, axis=-1)


def bbox_overlap_fraction(bbox: Array, origin_yx: Array, patch: int) -> Array:
    """Fraction of a PxP patch (at origin_yx) covered by ``bbox``.

    Args:
      bbox: (..., 4) (vmin, umin, vmax, umax).
      origin_yx: (..., 2) patch top-left.

    Returns:
      (...,) overlap area / patch area, in [0, 1].
    """
    pv0 = origin_yx[..., 0]
    pu0 = origin_yx[..., 1]
    pv1 = pv0 + patch
    pu1 = pu0 + patch
    iv = jnp.maximum(
        0.0, jnp.minimum(bbox[..., 2], pv1) - jnp.maximum(bbox[..., 0], pv0)
    )
    iu = jnp.maximum(
        0.0, jnp.minimum(bbox[..., 3], pu1) - jnp.maximum(bbox[..., 1], pu0)
    )
    return iv * iu / float(patch * patch)
