"""The plain reference: one EPIC session, written out in plain ``jax.numpy``.

It states the semantics the served path has to reproduce for the
configurations of this benchmark (sensor depth, every patch salient,
the two-phase sparse TRD with an adaptive candidate budget K), in the
order the paper gives them (arXiv 2606.15859, Sections 3.4-4.2):

1. the frame-bypass gate: process a frame whose mean absolute RGB
   difference to the last processed frame exceeds ``gamma``, the first
   frame, and a frame after ``theta`` bypassed ones;
2. on a processed frame, the corner-warp bounding box of every valid
   DC-buffer entry, its overlap with each frame patch, and the ``K``
   newest entries whose box covers a salient patch by ``o_min``;
3. for those candidates, every pixel warped into the current view,
   sampled bilinearly inside a ``window`` x ``window`` region centred on
   the warped box, and scored by mean absolute RGB difference and
   coverage;
4. each salient patch matched to the newest candidate with difference
   ``<= tau``, coverage ``>= c_min`` and box overlap ``>= o_min``; a
   match bumps the entry's popularity and last use, and every unmatched
   salient patch is inserted, the buffer keeping its ``capacity``
   highest-scoring entries (popularity minus ``w_recency`` per frame
   since last use; residents win ties);
5. between chunks, K grows one rung of the ladder after a chunk that
   truncated a candidate set, and shrinks one rung when the chunk's
   largest candidate count fits the rung below twice over.

Nothing here imports the program.  The arithmetic follows the program's
own ``ref`` path operation for operation, so that on one device the two
agree bit for bit; ``dtype`` lowers the precision of the sensor data
and the geometry (the control of the correctness check).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


class Params(NamedTuple):
    """The configuration's numbers that the semantics depend on."""

    frame: int
    patch: int
    capacity: int
    window: int
    tau: float
    o_min: float
    c_min: float
    gamma: float
    theta: int
    w_popularity: float
    w_recency: float
    focal_frac: float

    @classmethod
    def of(cls, cfg: Dict) -> "Params":
        return cls(*(cfg[f] for f in cls._fields))


class Buffer(NamedTuple):
    rgb: jax.Array  # (N, P, P, 3)
    depth: jax.Array  # (N, P, P)
    pose: jax.Array  # (N, 4, 4)
    origin: jax.Array  # (N, 2) row, col
    t: jax.Array  # (N,)
    t_last: jax.Array  # (N,)
    saliency: jax.Array  # (N,)
    popularity: jax.Array  # (N,)
    valid: jax.Array  # (N,) bool


class State(NamedTuple):
    ref_frame: jax.Array
    counter: jax.Array
    initialized: jax.Array
    buf: Buffer
    t: jax.Array


class Counts(NamedTuple):
    """Per-frame counts the comparison and the K rule read."""

    processed: jax.Array
    n_inserted: jax.Array
    n_full: jax.Array
    n_overflow: jax.Array
    buffer_valid: jax.Array


def init(p: Params, dtype=jnp.float32) -> State:
    n, s = p.capacity, p.patch
    buf = Buffer(
        rgb=jnp.zeros((n, s, s, 3), dtype),
        depth=jnp.ones((n, s, s), dtype),
        pose=jnp.broadcast_to(jnp.eye(4, dtype=dtype), (n, 4, 4)),
        origin=jnp.zeros((n, 2), jnp.float32),
        t=jnp.full((n,), -1.0, jnp.float32),
        t_last=jnp.full((n,), -1.0, jnp.float32),
        saliency=jnp.zeros((n,), jnp.float32),
        popularity=jnp.zeros((n,), jnp.float32),
        valid=jnp.zeros((n,), bool),
    )
    return State(
        jnp.zeros((p.frame, p.frame, 3), dtype), jnp.zeros((), jnp.int32),
        jnp.zeros((), bool), buf, jnp.zeros((), jnp.float32),
    )


# -- geometry ----------------------------------------------------------------


def _rigid(rot, trans):
    top = jnp.concatenate([rot, trans[..., :, None]], axis=-1)
    bottom = jnp.broadcast_to(
        jnp.array([0.0, 0.0, 0.0, 1.0], rot.dtype), rot.shape[:-2] + (1, 4)
    )
    return jnp.concatenate([top, bottom], axis=-2)


def relative(src, dst):
    """Source-camera to destination-camera transform, ``inv(dst) @ src``."""
    rot_t = jnp.swapaxes(dst[..., :3, :3], -1, -2)
    trans = -jnp.einsum("...ij,...j->...i", rot_t, dst[..., :3, 3], precision=HIGHEST)
    return jnp.matmul(_rigid(rot_t, trans), src, precision=HIGHEST)


def reproject(uv, depth, f, c, t_rel):
    """Lift pixels with depth, move them rigidly, project them again."""
    x = (uv[..., 0] - c) / f * depth
    y = (uv[..., 1] - c) / f * depth
    xyz = jnp.stack([x, y, depth], axis=-1)
    xyz = jnp.einsum("...ij,...j->...i", t_rel[..., :3, :3], xyz, precision=HIGHEST)
    xyz = xyz + t_rel[..., :3, 3]
    z = xyz[..., 2]
    front = z > EPS
    zs = jnp.where(front, z, 1.0)
    u = xyz[..., 0] / zs * f + c
    v = xyz[..., 1] / zs * f + c
    return jnp.stack([u, v], axis=-1), front


def corner_box(origin, corner_depth, f, c, t_rel, patch):
    """Warped bounding box ``(vmin, umin, vmax, umax)`` of a patch's four
    corners, and whether all four land in front of the camera."""
    q = jnp.float32(patch - 1)
    zeros = jnp.zeros_like(origin[..., 0])
    offs = jnp.stack(
        [
            jnp.stack([zeros, zeros], -1), jnp.stack([zeros, zeros + q], -1),
            jnp.stack([zeros + q, zeros], -1), jnp.stack([zeros + q, zeros + q], -1),
        ],
        axis=-2,
    )
    yx = origin[..., None, :] + offs
    uv = jnp.stack([yx[..., 1], yx[..., 0]], axis=-1)
    if t_rel.ndim > 2:
        t_rel = t_rel[..., None, :, :]
    uv2, front = reproject(uv, corner_depth, f, c, t_rel)
    box = jnp.stack(
        [
            jnp.min(uv2[..., 1], axis=-1), jnp.min(uv2[..., 0], axis=-1),
            jnp.max(uv2[..., 1], axis=-1), jnp.max(uv2[..., 0], axis=-1),
        ],
        axis=-1,
    )
    return box, jnp.all(front, axis=-1)


def overlap(box, origin, patch):
    """Share of a patch at ``origin`` that ``box`` covers."""
    iv = jnp.maximum(
        0.0, jnp.minimum(box[..., 2], origin[..., 0] + patch)
        - jnp.maximum(box[..., 0], origin[..., 0])
    )
    iu = jnp.maximum(
        0.0, jnp.minimum(box[..., 3], origin[..., 1] + patch)
        - jnp.maximum(box[..., 1], origin[..., 1])
    )
    return iv * iu / float(patch * patch)


def _corners(depth, patch):
    q = patch - 1
    return jnp.stack(
        [depth[..., 0, 0], depth[..., 0, q], depth[..., q, 0], depth[..., q, q]],
        axis=-1,
    )


def _sample(image, coords):
    h, w = image.shape[0], image.shape[1]
    u, v = coords[..., 0], coords[..., 1]
    u0, v0 = jnp.floor(u), jnp.floor(v)
    du, dv = u - u0, v - v0
    inside = (u0 >= 0) & (u0 + 1 <= w - 1) & (v0 >= 0) & (v0 + 1 <= h - 1)
    ui = jnp.clip(u0.astype(jnp.int32), 0, w - 2)
    vi = jnp.clip(v0.astype(jnp.int32), 0, h - 2)
    out = (
        image[vi, ui] * ((1 - du) * (1 - dv))[..., None]
        + image[vi, ui + 1] * (du * (1 - dv))[..., None]
        + image[vi + 1, ui] * ((1 - du) * dv)[..., None]
        + image[vi + 1, ui + 1] * (du * dv)[..., None]
    )
    return jnp.where(inside[..., None], out, 0.0), inside


def score(rgb, depth, origin, t_rel, frame, f, c, window):
    """One entry against the current frame: masked mean absolute RGB
    difference (1 where nothing lands) and coverage."""
    patch = rgb.shape[0]
    h, w = frame.shape[0], frame.shape[1]
    rr = jnp.arange(patch, dtype=jnp.float32)
    vv, uu = jnp.meshgrid(rr, rr, indexing="ij")
    grid = jnp.stack([origin[1] + uu, origin[0] + vv], axis=-1)
    coords, front = reproject(grid, depth, f, c, t_rel)
    box, box_front = corner_box(origin, _corners(depth, patch), f, c, t_rel, patch)
    oy = jnp.clip(jnp.floor(0.5 * (box[0] + box[2]) - window / 2.0), 0.0, float(h - window))
    ox = jnp.clip(jnp.floor(0.5 * (box[1] + box[3]) - window / 2.0), 0.0, float(w - window))
    win = jax.lax.dynamic_slice(
        frame, (oy.astype(jnp.int32), ox.astype(jnp.int32), 0), (window, window, 3)
    )
    sampled, inside = _sample(win, coords - jnp.stack([ox, oy]))
    ok = front & inside
    n_ok = jnp.sum(ok)
    absdiff = jnp.mean(jnp.abs(sampled - rgb), axis=-1)
    diff = jnp.sum(jnp.where(ok, absdiff, 0.0)) / jnp.maximum(n_ok, 1)
    diff = jnp.where(n_ok > 0, diff, 1.0)
    coverage = jnp.where(box_front, n_ok / float(patch * patch), 0.0)
    return diff, coverage


# -- one frame -----------------------------------------------------------------


def _patches(frame, patch):
    h, w, ch = frame.shape
    g = h // patch
    x = frame[: g * patch, : g * patch].reshape(g, patch, g, patch, ch)
    return x.transpose(0, 2, 1, 3, 4).reshape(g * g, patch, patch, ch)


def _origins(g, patch):
    oy, ox = jnp.meshgrid(
        jnp.arange(g, dtype=jnp.float32) * patch,
        jnp.arange(g, dtype=jnp.float32) * patch, indexing="ij",
    )
    return jnp.stack([oy.ravel(), ox.ravel()], axis=-1)


def _update(buf: Buffer, p: Params, k: int, frame, depth, pose, now):
    """Steps 2-4 on a processed frame."""
    s, n = p.patch, p.capacity
    f = jnp.asarray(p.focal_frac * p.frame, frame.dtype)
    c = jnp.asarray(p.frame / 2.0, frame.dtype)
    patches = _patches(frame, s)
    origins = _origins(p.frame // s, s)
    m = origins.shape[0]
    salient = jnp.ones((m,), bool)
    t_rel = relative(buf.pose, pose)
    # Step 2: corner boxes, overlaps, the K newest passing entries.
    box, _ = corner_box(buf.origin, _corners(buf.depth, s), f, c, t_rel, s)
    ov_ok = overlap(box[:, None, :], origins[None, :, :], s) >= p.o_min
    passes = jnp.any(ov_ok & salient[None, :], axis=1) & buf.valid
    k = min(k, n)
    _, cand = jax.lax.top_k(jnp.where(passes, buf.t, -jnp.inf), k)
    real = passes[cand]
    n_pass = jnp.sum(passes.astype(jnp.int32))
    n_full = jnp.sum(real.astype(jnp.int32))
    cand_valid = buf.valid[cand] & real
    # Step 3: the candidates' pixels.
    diff, cov = jax.vmap(score, in_axes=(0, 0, 0, 0, None, None, None, None))(
        buf.rgb[cand], buf.depth[cand], buf.origin[cand], t_rel[cand],
        frame, f, c, p.window,
    )
    ok = (diff <= p.tau) & (cov >= p.c_min) & cand_valid
    feasible = ok[:, None] & ov_ok[cand] & salient[None, :] & cand_valid[:, None]
    # Step 4: newest match, popularity, insertion.
    pick = jnp.argmax(jnp.where(feasible, buf.t[cand][:, None], -jnp.inf), axis=0)
    matched = jnp.any(feasible, axis=0)
    chosen = cand[pick]
    pop = buf.popularity + jnp.zeros_like(buf.popularity).at[chosen].add(
        matched.astype(jnp.float32)
    )
    hit = jnp.zeros_like(buf.valid).at[chosen].max(matched)
    t_last = jnp.where(hit, jnp.asarray(now, jnp.float32), buf.t_last)
    fresh = salient & ~matched
    t_b = jnp.broadcast_to(now, (m,)).astype(jnp.float32)
    cand_buf = Buffer(
        rgb=jnp.concatenate([buf.rgb, patches], 0),
        depth=jnp.concatenate([buf.depth, _patches(depth[..., None], s)[..., 0]], 0),
        pose=jnp.concatenate([buf.pose, jnp.broadcast_to(pose, (m, 4, 4))], 0),
        origin=jnp.concatenate([buf.origin, origins], 0),
        t=jnp.concatenate([buf.t, t_b], 0),
        t_last=jnp.concatenate([t_last, t_b], 0),
        saliency=jnp.concatenate([buf.saliency, jnp.ones((m,), jnp.float32)], 0),
        popularity=jnp.concatenate([pop, jnp.ones((m,))], 0),
        valid=jnp.concatenate([buf.valid, fresh], 0),
    )
    keep_score = jnp.where(
        cand_buf.valid,
        p.w_popularity * cand_buf.popularity - p.w_recency * (now - cand_buf.t_last),
        -jnp.inf,
    )
    penalty = jnp.arange(n + m, dtype=jnp.float32) * 1e-7
    _, keep = jax.lax.top_k(
        jnp.where(jnp.isneginf(keep_score), keep_score, keep_score - penalty), n
    )
    buf = jax.tree.map(lambda x: x[keep], cand_buf)
    return buf, jnp.sum(fresh.astype(jnp.int32)), n_full, n_pass - n_full


@functools.partial(jax.jit, static_argnums=(1, 2))
def chunk_step(state: State, p: Params, k: int, frames, poses, depth):
    """Steps 1-4 over the frames of one chunk at candidate budget ``k``."""

    def frame_step(st: State, x):
        frame, pose, dep = x
        diff = jnp.mean(jnp.abs(frame - st.ref_frame))
        process = (diff > p.gamma) | (st.counter >= p.theta) | ~st.initialized
        zero = jnp.zeros((), jnp.int32)

        def run(buf):
            return _update(buf, p, k, frame, dep, pose, st.t)

        def skip(buf):
            return buf, zero, zero, zero

        buf, n_ins, n_full, n_over = jax.lax.cond(process, run, skip, st.buf)
        st = State(
            jnp.where(process, frame, st.ref_frame),
            jnp.where(process, 0, st.counter + 1),
            jnp.ones((), bool), buf, st.t + 1.0,
        )
        valid = jnp.sum(buf.valid.astype(jnp.int32))
        return st, Counts(process, n_ins, n_full, n_over, valid)

    return jax.lax.scan(frame_step, state, (frames, poses, depth))


class Result(NamedTuple):
    """What one stream's session leaves: its retained patches and counts."""

    retained: Dict[str, np.ndarray]
    counters: Dict[str, object]


RETAINED = ("rgb", "t", "origin", "valid", "saliency", "popularity", "t_last")


def run_stream(
    p: Params,
    ladder: Sequence[int],
    chunks: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
    dtype=jnp.float32,
    shrink_margin: int = 2,
) -> Result:
    """One session over ``chunks`` of ``(frames, poses, depth)``."""
    state = init(p, dtype)
    rung, ks = 0, []
    n_frames = n_proc = n_ins = 0
    valid = 0
    for frames, poses, depth in chunks:
        k = ladder[rung]
        ks.append(k)
        state, cnt = chunk_step(
            state, p, k,
            jnp.asarray(frames, dtype), jnp.asarray(poses, dtype),
            jnp.asarray(depth, dtype),
        )
        cnt = jax.device_get(cnt)
        n_frames += frames.shape[0]
        n_proc += int(np.sum(cnt.processed))
        n_ins += int(np.sum(cnt.n_inserted))
        valid = int(cnt.buffer_valid[-1])
        if int(np.sum(cnt.n_overflow)) > 0 and rung < len(ladder) - 1:
            rung += 1
        elif rung > 0 and int(np.max(cnt.n_full)) * shrink_margin <= ladder[rung - 1]:
            rung -= 1
    buf = jax.device_get(state.buf)
    retained = {f: np.asarray(getattr(buf, f)) for f in RETAINED}
    if dtype != jnp.float32:
        retained = {
            f: a.astype(np.float32) if a.dtype == dtype else a
            for f, a in retained.items()
        }
    return Result(
        retained,
        {
            "n_frames": n_frames, "n_processed": n_proc, "n_inserted": n_ins,
            "buffer_valid": valid, "k_trajectory": ks,
        },
    )


EXACT_COUNTERS = ("n_frames", "n_processed", "buffer_valid")


class Diff(NamedTuple):
    """How a served session differs from the reference's."""

    entries: int  # retained entries (buffer slots) with any field different
    counters: int  # exact counters that differ, and chunks whose K differs
    inserted_gap: int  # |patches inserted, served - reference|
    lines: List[str]  # one per field that differs


def compare(got: Result, want: Result) -> Diff:
    """Entry by entry and counter by counter."""
    lines: List[str] = []
    cap = want.retained["valid"].shape[0]
    off = np.zeros(cap, bool)
    for f in RETAINED:
        a, b = got.retained[f], want.retained[f]
        if a.shape != b.shape:
            off[:] = True
            lines.append(f"{f}: shape {a.shape} vs {b.shape}")
            continue
        d = (a != b).reshape(cap, -1).any(axis=1)
        if d.any():
            off |= d
            lines.append(f"{f}: {int(d.sum())} entries differ")
    counters = 0
    for f in EXACT_COUNTERS:
        g, w = got.counters.get(f), want.counters[f]
        if g != w:
            counters += 1
            lines.append(f"{f}: {g} vs {w}")
    gk = list(got.counters.get("k_trajectory", []))
    wk = list(want.counters["k_trajectory"])
    k_off = sum(a != b for a, b in zip(gk, wk)) + abs(len(gk) - len(wk))
    if k_off:
        counters += k_off
        lines.append(f"k_trajectory: {k_off} chunks differ")
    gi, wi = got.counters.get("n_inserted", 0), want.counters["n_inserted"]
    if gi != wi:
        lines.append(f"n_inserted: {gi} vs {wi}")
    return Diff(int(off.sum()), counters, abs(gi - wi), lines)
