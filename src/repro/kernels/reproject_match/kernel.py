"""Pallas TPU kernel for the reproject-match op (EPIC TRD hot-spot).

Hardware mapping (paper Section 4.1 -> TPU):

* The EPIC accelerator's *reprojection engine* walks DC-buffer entries,
  reprojects each bounding box, and only then runs the expensive pixel-level
  compare. On TPU the same structure becomes a grid over entries with each
  grid step owning one entry's P x P pixels in VMEM.
* The ASIC's irregular gather (bilinear sampling of the current frame at
  warped coordinates) has no efficient TPU analogue — TPU vector memory has
  no per-lane gather. We therefore *rewrite bilinear sampling as dense
  matmuls* against one-hot interpolation operators built with
  ``broadcasted_iota``: for warped pixel k and window row r / column c,

      A[r, k] = (r == floor(v_k)) (1 - dv_k) + (r == floor(v_k) + 1) dv_k
      B[c, k] = (c == floor(u_k)) (1 - du_k) + (c == floor(u_k) + 1) du_k

      sampled[ch, k] = sum_c B[c, k] * (win[ch]^T @ A)[c, k]

  This trades ~W x more MACs for perfectly regular MXU work — the canonical
  TPU bargain (dense masked compute replaces irregular skipping). The MACs
  are tiny (3*K*W*W ~ 0.8M for P=16, W=32) against the MXU's 197 TFLOP/s.
* The ASIC's bbox prefilter survives as the *window*: a ``window x window``
  slice of the frame centred on the warped bbox is the only frame data the
  entry's compare ever touches. The frame therefore stays in HBM
  (``memory_space=pl.ANY``); each entry DMAs the smallest tile-aligned
  band holding its window (HBM slices start on the (8, 128) tile grid,
  see :func:`band_shape`) and rotates the window to the band's corner —
  the working set is independent of the frame size.

Layouts (lane-dense, so nothing pads a 3-wide minor dimension to 128):

* the ``K = P * P`` warped pixels of an entry run along lanes — every
  per-pixel quantity is a ``(1, K)`` row, entry pixels are ``(3, K)``;
* the frames are channel-planar ``(B, 3, H, W)`` in HBM: a vmapped call
  (a pool of streams) becomes the grid's leading frame-batch axis
  (:func:`fold_vmap`), since Pallas cannot vmap an HBM operand itself;
* per-entry scalars (intrinsics, origins, the 4x4 transforms) sit in SMEM;
* outputs are blocks of 8 rows, one ``[diff, coverage, vmin, umin, vmax,
  umax, 0, 0]`` row per entry.

VMEM working set per grid step (P=16, W=32, fp32): entry pixels 3*K*4 =
3 KiB, band 3*40*256*4 = 120 KiB, interpolators 2*W*K*4 = 64 KiB, output
block 256 B — far under the scoped-VMEM default at any frame size.

Interpret vs compiled: the kernel-level functions take an explicit
``interpret`` flag. :func:`on_platform` runs one compiled where the
program is lowered for TPU and interpreted everywhere else (the CPU test
path) — the choice follows the lowering platform, never a user option.
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import geometry as geo

Array = jax.Array

_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST

# Output rows per block: Mosaic tiles the second-minor dimension by 8.
ROWS = 8


def on_platform(kernel_fn, *args, **static):
    """Call a Pallas entry point compiled on TPU, interpreted on CPU.

    ``jax.lax.platform_dependent`` resolves the branch when the program is
    lowered, so a program lowered for TPU — on the chip or for a described
    topology — carries the Mosaic kernel, and one lowered for CPU the
    interpreter.  Lowering for any other platform fails: there is no
    silent interpreter fallback.
    """
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel_fn, **static, interpret=False),
        cpu=functools.partial(kernel_fn, **static, interpret=True),
    )


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def band_shape(window: int, frame_h: int, frame_w: int) -> Tuple[int, int]:
    """Rows x columns of the frame band one entry DMAs from HBM.

    HBM slices start on the (8, 128) tile grid and Mosaic rotates only
    whole tiles, so the band is the window rounded out to that grid —
    ``window + 7`` rows and ``window + 127`` columns, rounded up to whole
    tiles — and at most the frame as padded by :func:`kernel_operands`.
    """
    return (
        min(_round_up(window + 7, 8), _round_up(frame_h, 8)),
        min(_round_up(window + 127, 128), _round_up(frame_w, 128)),
    )


def _entry_scores(
    intr_ref,  # SMEM (1, 3) [f, cx, cy]
    origin_ref,  # SMEM (E, 1, 2) entry top-left (row, col)
    trel_ref,  # SMEM (E, 1, 16) source->current transforms, row-major
    rgb_ref,  # VMEM (E, 3, K) entry pixels I_c, channel-planar
    depth_ref,  # VMEM (E, 1, K) entry depth d_c
    frame_ref,  # HBM (B, 3, H, W) current frames F_t, channel-planar
    band_ref,  # VMEM scratch (3, BH, BW), see band_shape
    sem,  # DMA semaphore
    *,
    patch: int,
    window: int,
    frame_h: int,
    frame_w: int,
    e: int = 0,
):
    """Shared kernel body: warp one entry, sample, and score it.

    Returns ``(1, 1)`` vectors ``(diff, coverage, vmin, umin, vmax,
    umax)``.  Factored out so the one-entry-per-step kernel, the
    entry-tiled kernel and the fused TSRC kernel (``fused.py``) run the
    *same ops in the same order* — their outputs are bitwise identical.
    ``e`` indexes the entry within the grid step's block; grid axis 0
    indexes the frame batch.
    """
    p = patch
    k = p * p
    f = intr_ref[0, 0]
    cx = intr_ref[0, 1]
    cy = intr_ref[0, 2]
    oy = origin_ref[e, 0, 0]
    ox = origin_ref[e, 0, 1]
    t = [trel_ref[e, 0, j] for j in range(12)]

    # --- Warp the entry's pixel grid into the current view (Eq. 1). --------
    kk = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1).astype(jnp.float32)
    row = jnp.floor(kk / p)  # exact: small integers
    vv = row + oy  # rows (v)
    uu = (kk - row * p) + ox  # cols (u)
    depth = depth_ref[e]  # (1, K)
    x1 = (uu - cx) / f * depth
    y1 = (vv - cy) / f * depth
    z1 = depth
    x2 = t[0] * x1 + t[1] * y1 + t[2] * z1 + t[3]
    y2 = t[4] * x1 + t[5] * y1 + t[6] * z1 + t[7]
    z2 = t[8] * x1 + t[9] * y1 + t[10] * z1 + t[11]
    in_front = z2 > _EPS
    safe_z = jnp.where(in_front, z2, 1.0)
    u2 = x2 / safe_z * f + cx  # (1, K) warped u
    v2 = y2 / safe_z * f + cy  # (1, K) warped v

    # --- Corner bbox (the reprojection engine's prefilter). ----------------
    corner = (kk == 0) | (kk == p - 1) | (kk == k - p) | (kk == k - 1)
    inf = jnp.float32(jnp.inf)
    vmin = jnp.min(jnp.where(corner, v2, inf), axis=1, keepdims=True)
    vmax = jnp.max(jnp.where(corner, v2, -inf), axis=1, keepdims=True)
    umin = jnp.min(jnp.where(corner, u2, inf), axis=1, keepdims=True)
    umax = jnp.max(jnp.where(corner, u2, -inf), axis=1, keepdims=True)
    n_behind = jnp.sum(
        jnp.where(corner & ~in_front, 1.0, 0.0), axis=1, keepdims=True
    )
    bbox_valid = n_behind == 0.0

    # --- DMA the tile-aligned frame band holding the window. ---------------
    wcy = 0.5 * (vmin + vmax)
    wcx = 0.5 * (umin + umax)
    woy = jnp.clip(jnp.floor(wcy - window / 2.0), 0.0, float(frame_h - window))
    wox = jnp.clip(jnp.floor(wcx - window / 2.0), 0.0, float(frame_w - window))
    bh, bw = band_ref.shape[1], band_ref.shape[2]
    woy_i = woy.astype(jnp.int32)[0, 0]
    wox_i = wox.astype(jnp.int32)[0, 0]
    r0 = pl.multiple_of(
        jnp.minimum((woy_i // 8) * 8, _round_up(frame_h, 8) - bh), 8
    )
    c0 = pl.multiple_of(
        jnp.minimum((wox_i // 128) * 128, _round_up(frame_w, 128) - bw), 128
    )
    copy = pltpu.make_async_copy(
        frame_ref.at[pl.program_id(0), :, pl.ds(r0, bh), pl.ds(c0, bw)],
        band_ref,
        sem,
    )
    copy.start()

    # --- Bilinear sampling as dense matmuls (see module docstring). --------
    lu = u2 - wox  # window-local u per warped pixel
    lv = v2 - woy
    u0 = jnp.floor(lu)
    v0 = jnp.floor(lv)
    du = lu - u0
    dv = lv - v0
    in_win = (
        (u0 >= 0) & (u0 + 1 <= window - 1) & (v0 >= 0) & (v0 + 1 <= window - 1)
    )
    u0c = jnp.clip(u0, 0.0, float(window - 2))
    v0c = jnp.clip(v0, 0.0, float(window - 2))

    rows = jax.lax.broadcasted_iota(jnp.int32, (window, k), 0).astype(
        jnp.float32
    )
    a = jnp.where(rows == v0c, 1.0 - dv, 0.0) + jnp.where(
        rows == v0c + 1.0, dv, 0.0
    )  # (W, K) row interpolator
    b = jnp.where(rows == u0c, 1.0 - du, 0.0) + jnp.where(
        rows == u0c + 1.0, du, 0.0
    )  # (W, K) col interpolator

    # Rotate the window to the band's top-left corner (exact data
    # movement), then slice it out statically.
    shift_r = (bh - (woy_i - r0)) % bh
    shift_c = (bw - (wox_i - c0)) % bw
    copy.wait()
    absdiff = None
    for ch in range(3):
        band = pltpu.roll(pltpu.roll(band_ref[ch], shift_r, 0), shift_c, 1)
        win = band[:window, :window]  # (W rows, W cols)
        t1 = jax.lax.dot_general(
            win,
            a,
            (((0,), (0,)), ((), ())),
            precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (W cols, K): frame rows interpolated
        sampled = jnp.sum(b * t1, axis=0, keepdims=True)  # (1, K)
        term = jnp.abs(sampled - rgb_ref[e, pl.ds(ch, 1), :])
        absdiff = term if absdiff is None else absdiff + term
    absdiff = absdiff / 3.0

    # --- Masked mean |I_c - sampled| + coverage. ----------------------------
    valid = jnp.where(in_front & in_win, 1.0, 0.0)  # (1, K)
    nvalid = jnp.sum(valid, axis=1, keepdims=True)
    denom = jnp.maximum(nvalid, 1.0)
    diff = jnp.sum(absdiff * valid, axis=1, keepdims=True) / denom
    diff = jnp.where(nvalid > 0, diff, 1.0)
    coverage = jnp.where(bbox_valid, nvalid / float(k), 0.0)
    return diff, coverage, vmin, umin, vmax, umax


def _score_row(scores, width: int = 8) -> Array:
    """Pack ``(1, 1)`` scores into one ``(1, width)`` output row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = jnp.zeros((1, width), jnp.float32)
    for j, s in enumerate(scores):
        row = jnp.where(lane == j, s, row)
    return row


def _put_row(out_ref, r, row) -> None:
    """Write ``row`` into row ``r`` of an 8-row output block."""
    rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out_ref[...] = jnp.where(rows == r, row, out_ref[...])


def fold_vmap(batched_fn: Callable) -> Callable:
    """Unbatched entry point over ``batched_fn``, whose operands and
    results all carry a leading frame-batch axis.

    Pallas' own vmap rule cannot batch an operand left in HBM
    (``pl.ANY``), so a vmapped call — a pool of streams — instead folds
    its axis into that batch axis: one kernel launch over a
    ``(batch, entries)`` grid, nested vmaps included.
    """

    def broadcast(axis_size, in_batched, args):
        return jax.tree.map(
            lambda a, b: a if b else jnp.broadcast_to(a, (axis_size, *a.shape)),
            tuple(args),
            tuple(in_batched),
        )

    def all_batched(out):
        return jax.tree.map(lambda _: True, out)

    @jax.custom_batching.custom_vmap
    def batched(*args):
        return batched_fn(*args)

    @batched.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = broadcast(axis_size, in_batched, args)
        out = batched(*jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), args))
        out = jax.tree.map(lambda o: o.reshape(axis_size, -1, *o.shape[1:]), out)
        return out, all_batched(out)

    @jax.custom_batching.custom_vmap
    def single(*args):
        out = batched(*jax.tree.map(lambda a: a[None], args))
        return jax.tree.map(lambda o: o[0], out)

    @single.def_vmap
    def _batch(axis_size, in_batched, *args):
        out = batched(*broadcast(axis_size, in_batched, args))
        return out, all_batched(out)

    return single


def pad_entries(entry_rgb, entry_depth, entry_origin, t_rel, multiple: int):
    """Pad the entry axis to a multiple with benign entries (identity
    transform, unit depth); callers slice the padding rows off."""
    n, p = entry_rgb.shape[0], entry_rgb.shape[1]
    pad = -n % multiple
    if pad:
        entry_rgb = jnp.concatenate(
            [entry_rgb, jnp.zeros((pad, p, p, 3), entry_rgb.dtype)], 0
        )
        entry_depth = jnp.concatenate(
            [entry_depth, jnp.ones((pad, p, p), entry_depth.dtype)], 0
        )
        entry_origin = jnp.concatenate(
            [entry_origin, jnp.zeros((pad, 2), entry_origin.dtype)], 0
        )
        t_rel = jnp.concatenate(
            [t_rel, jnp.broadcast_to(jnp.eye(4, dtype=t_rel.dtype), (pad, 4, 4))],
            0,
        )
    return entry_rgb, entry_depth, entry_origin, t_rel


def kernel_operands(entry_rgb, entry_depth, entry_origin, t_rel, frame, intr):
    """Re-lay one frame's op inputs for the kernels (module docstring)."""
    n, p = entry_rgb.shape[0], entry_rgb.shape[1]
    h, w = frame.shape[0], frame.shape[1]
    k = p * p
    intr_vec = jnp.stack(
        [
            jnp.asarray(intr.f, jnp.float32),
            jnp.asarray(intr.cx, jnp.float32),
            jnp.asarray(intr.cy, jnp.float32),
        ]
    )
    return (
        intr_vec.reshape(1, 3),
        entry_origin.astype(jnp.float32).reshape(n, 1, 2),
        t_rel.astype(jnp.float32).reshape(n, 1, 16),
        entry_rgb.reshape(n, k, 3).transpose(0, 2, 1),
        entry_depth.reshape(n, 1, k),
        jnp.pad(
            frame.transpose(2, 0, 1),
            ((0, 0), (0, -h % 8), (0, -w % 128)),
        ),  # whole (8, 128) tiles, so every band slice is tile-aligned
    )


def launch(kernel, operands, *, tile: int, out_cols, window: int, interpret: bool):
    """``pallas_call`` over a ``(batch, entries / tile)`` grid.

    ``operands`` are :func:`kernel_operands` stacked over a leading frame
    batch; ``tile`` entries are owned by each grid step.  Each output is
    ``(batch, entries, cols)`` for ``cols`` in ``out_cols``, in blocks of
    8 rows (``tile`` rows when tiled): a one-entry step writes its row of
    the block it shares with its 7 neighbours.
    """
    bsz, n_pad, _, k = operands[3].shape
    _, _, h, w = operands[5].shape
    rows = ROWS if tile == 1 else tile
    sq = pl.Squeezed()

    def entry_block(*shape):
        return pl.BlockSpec(
            (sq, tile, *shape), lambda b, i: (b, i, *(0,) * len(shape))
        )

    def smem_entry_block(*shape):
        return pl.BlockSpec(
            (sq, tile, *shape),
            lambda b, i: (b, i, *(0,) * len(shape)),
            memory_space=pltpu.SMEM,
        )

    return pl.pallas_call(
        kernel,
        grid=(bsz, n_pad // tile),
        in_specs=[
            pl.BlockSpec((sq, 1, 3), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.SMEM),  # intrinsics
            smem_entry_block(1, 2),  # origins
            smem_entry_block(1, 16),  # transforms
            entry_block(3, k),  # entry pixels
            entry_block(1, k),  # entry depth
            pl.BlockSpec(memory_space=pl.ANY),  # frames stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((sq, rows, c), lambda b, i: (b, i * tile // rows, 0))
            for c in out_cols
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n_pad, c), jnp.float32) for c in out_cols
        ],
        scratch_shapes=[
            pltpu.VMEM((3, *band_shape(window, h, w)), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(*operands)


def _tiled_kernel(
    intr_ref, origin_ref, trel_ref, rgb_ref, depth_ref, frame_ref,
    out_ref,  # (rows, 8) packed output block, see launch
    band_ref, sem,
    *,
    patch: int,
    window: int,
    frame_h: int,
    frame_w: int,
    tile_n: int,
):
    first = pl.program_id(1) * tile_n
    for j in range(tile_n):  # static unroll over the tile's entries
        scores = _entry_scores(
            intr_ref, origin_ref, trel_ref, rgb_ref, depth_ref, frame_ref,
            band_ref, sem,
            patch=patch, window=window, frame_h=frame_h, frame_w=frame_w, e=j,
        )
        _put_row(out_ref, (first + j) % out_ref.shape[0], _score_row(scores))


def _scores(out, n):
    return out[:n, 0], out[:n, 1], out[:n, 2:6]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def reproject_match_pallas(
    entry_rgb: Array,  # (N, P, P, 3)
    entry_depth: Array,  # (N, P, P)
    entry_origin: Array,  # (N, 2)
    t_rel: Array,  # (N, 4, 4)
    frame: Array,  # (H, W, 3)
    intr: geo.Intrinsics,
    *,
    window: int = 64,
    interpret: bool,
) -> Tuple[Array, Array, Array]:
    """Pallas TPU implementation of the reproject-match op.

    Same contract as
    :func:`repro.kernels.reproject_match.ref.reproject_match_ref`.
    One entry per grid step; the entry axis is padded to a multiple of
    8 so each output block holds 8 entries' rows.
    """
    n, p = entry_rgb.shape[0], entry_rgb.shape[1]
    h, w = frame.shape[0], frame.shape[1]
    args = pad_entries(entry_rgb, entry_depth, entry_origin, t_rel, ROWS)
    kernel = functools.partial(
        _tiled_kernel, patch=p, window=window, frame_h=h, frame_w=w, tile_n=1
    )

    def batched(*operands):
        return launch(
            kernel, operands, tile=1, out_cols=(8,), window=window,
            interpret=interpret,
        )[0]

    out = fold_vmap(batched)(*kernel_operands(*args, frame, intr))
    return _scores(out, n)


# ---------------------------------------------------------------------------
# Entry-tiled variant: TILE_N entries per grid step.
# ---------------------------------------------------------------------------

# Entries owned by one grid step.  The one-entry-per-step layout above
# pays per-step dispatch/pipelining overhead that dominates at the small
# candidate counts the sparse-TRD prefilter produces (K ~ 16-32); eight
# entries per step amortises it while keeping the VMEM working set
# (8 entry tiles + one band) comfortably bounded.
TILE_N = 8


@functools.partial(
    jax.jit, static_argnames=("window", "tile_n", "interpret")
)
def reproject_match_pallas_tiled(
    entry_rgb: Array,  # (N, P, P, 3)
    entry_depth: Array,  # (N, P, P)
    entry_origin: Array,  # (N, 2)
    t_rel: Array,  # (N, 4, 4)
    frame: Array,  # (H, W, 3)
    intr: geo.Intrinsics,
    *,
    window: int = 64,
    tile_n: int = TILE_N,
    interpret: bool,
) -> Tuple[Array, Array, Array]:
    """Entry-tiled Pallas reproject-match: ``tile_n`` entries per grid step.

    Same contract (and bitwise the same per-entry scores — both run
    :func:`_entry_scores`) as :func:`reproject_match_pallas`, with
    ``grid=(ceil(N / tile_n),)`` instead of ``grid=(N,)``.  Inputs are
    padded to a tile multiple with benign entries (identity transform,
    unit depth) and the padding rows are sliced off the output.
    """
    n, p = entry_rgb.shape[0], entry_rgb.shape[1]
    h, w = frame.shape[0], frame.shape[1]
    tile = max(1, min(tile_n, n)) if n else 1
    args = pad_entries(entry_rgb, entry_depth, entry_origin, t_rel, tile)
    kernel = functools.partial(
        _tiled_kernel, patch=p, window=window, frame_h=h, frame_w=w,
        tile_n=tile,
    )

    def batched(*operands):
        return launch(
            kernel, operands, tile=tile, out_cols=(8,), window=window,
            interpret=interpret,
        )[0]

    out = fold_vmap(batched)(*kernel_operands(*args, frame, intr))
    return _scores(out, n)
