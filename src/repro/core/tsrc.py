"""Temporal-Spatial Redundancy Check (TSRC) — EPIC paper Section 3.4.

Per processed frame:

  1. SRD: the HIR module marks salient patches (Section 3.3).
  2. TRD: every valid DC-buffer entry is warped into the current view
     (Eq. 1, via the reproject-match op) and scored against the frame.
  3. Bounding-box overlap (the accelerator's prefilter, Section 4.1.1)
     associates warped entries with current-frame patches.
  4. A current patch *matches* the newest entry whose warped content is
     RGB-close (diff <= tau), sufficiently covering (coverage >= c_min) and
     spatially overlapping (overlap >= o_min). Matches bump the entry's
     popularity P_c; non-matching salient patches are inserted.

The dense-parallel formulation computes all (entry x patch) pair scores and
selects with masks — the TPU-native replacement for the ASIC's sequential
newest-first early-exit scan (equivalence property-tested in
tests/test_tsrc.py).

With ``TSRCConfig.prefilter_k > 0`` the expensive pixel-level compare runs
only on the K newest entries passing the bbox prefilter (the accelerator's
actual two-phase schedule, Section 4.1.1) — bit-identical to dense whenever
at most K entries pass; see ``kernels/reproject_match/sparse.py`` and the
``n_prefilter_overflow`` counter.

Sparse TRD v2 makes the sparsity two-sided and backend-complete:

* ``TSRCConfig.patch_k > 0`` mirrors the entry-side candidate select
  onto the *patch* axis: the match mask and ``dcb.newest_match`` run on
  ``(K, P_k)`` compacted slabs (salient-patch compaction, see
  ``compact_salient_patches``) instead of ``(K, M)`` — bit-identical to
  the dense patch axis whenever at most ``P_k`` salient patches exist;
  ``n_patch_overflow`` counts truncations.
* A backend's ``fused_match`` capability now *composes* with the
  prefilter instead of being bypassed by it: the fused kernel runs
  directly on the gathered ``(K, ...)`` candidate slabs and its
  per-(entry, patch) mask rows feed the (optionally compacted)
  association — bitwise the scores ``"pallas"`` produces on the same
  slabs.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.api.registry import BackendValidatedConfig, get_backend
from repro.core import dc_buffer as dcb
from repro.core import geometry as geo
from repro.kernels.reproject_match import sparse as sparse_mod
from repro.kernels.reproject_match.ops import reproject_match

Array = jax.Array


class _TSRCConfig(NamedTuple):
    tau: float = 0.08  # RGB-difference match threshold (paper's tau)
    o_min: float = 0.5  # min bbox overlap fraction of a patch
    c_min: float = 0.6  # min warped-pixel coverage of an entry
    window: int = 64  # reproject-match sampling window
    backend: str = "ref"  # reproject-match backend (registry key)
    prefilter_k: int = 0  # 0 = dense TRD; K > 0 = sparse top-K candidates
    patch_k: int = 0  # 0 = dense patch axis; P_k > 0 = salient compaction


class TSRCConfig(BackendValidatedConfig, _TSRCConfig):
    """TSRC thresholds + backend selection.

    Construction (and ``_replace``) fails fast on an unregistered
    ``backend`` (listing the available reproject-match registry keys) or
    a negative ``prefilter_k`` / ``patch_k`` — any of which would
    otherwise only surface deep inside the jitted scan.

    ``prefilter_k = 0`` runs the dense TRD (every valid entry fully
    warped and pixel-scored); ``prefilter_k = K > 0`` runs the two-phase
    sparse path of the EPIC accelerator (Section 4.1.1): a cheap corner
    -warp bbox prefilter over all entries, then the full reproject-match
    on only the K newest entries whose bbox overlaps a salient patch —
    bit-identical to dense whenever at most K entries pass (see
    ``kernels/reproject_match/sparse.py``).

    ``patch_k = P_k > 0`` additionally compacts the *patch* axis of the
    match algebra to the top ``P_k`` salient patch slots (bit-identical
    whenever at most ``P_k`` salient patches exist); it implies the
    sparse TRD machinery — with ``prefilter_k = 0`` the candidate set is
    simply every entry (never truncating the entry axis).
    """

    __slots__ = ()


class TSRCStats(NamedTuple):
    """Per-frame counters (also drive the energy model)."""

    n_salient: Array  # patches passing SRD
    n_matched: Array  # patches found redundant (popularity bumped)
    n_inserted: Array  # new DC-buffer entries
    n_bbox_checks: Array  # bbox reprojections performed (= valid entries)
    n_full_checks: Array  # entries fully pixel-scored (sparse: real
    #   candidate count; dense: entries the ASIC *would* score, i.e.
    #   bbox-overlapping a salient patch — the two agree when no
    #   prefilter truncation occurs)
    buffer_valid: Array  # occupancy after the step
    n_prefilter_overflow: Array  # passing entries truncated by top-K (0 dense)
    n_patch_overflow: Array  # salient patches truncated by top-P_k (0 dense)
    n_patch_checked: Array  # compacted patch slots gathered (0 = no
    #   patch compaction ran; drives the measured patch-read traffic)


def extract_patches(frame: Array, patch: int) -> Tuple[Array, Array]:
    """Split (H, W, 3) frame into non-overlapping PxP patches.

    Returns:
      patches: (G*G, P, P, 3); origins: (G*G, 2) top-left (row, col).
    """
    h, w, c = frame.shape
    gy, gx = h // patch, w // patch
    x = frame[: gy * patch, : gx * patch]
    x = x.reshape(gy, patch, gx, patch, c).transpose(0, 2, 1, 3, 4)
    patches = x.reshape(gy * gx, patch, patch, c)
    oy, ox = jnp.meshgrid(
        jnp.arange(gy, dtype=jnp.float32) * patch,
        jnp.arange(gx, dtype=jnp.float32) * patch,
        indexing="ij",
    )
    origins = jnp.stack([oy.ravel(), ox.ravel()], axis=-1)
    return patches, origins


def extract_depth_patches(depth: Array, patch: int) -> Array:
    """Split (H, W) depth map into (G*G, P, P) crops (same order)."""
    h, w = depth.shape
    gy, gx = h // patch, w // patch
    d = depth[: gy * patch, : gx * patch]
    d = d.reshape(gy, patch, gx, patch).transpose(0, 2, 1, 3)
    return d.reshape(gy * gx, patch, patch)


def tsrc_step(
    buf: dcb.DCBuffer,
    buf_cfg: dcb.DCBufferConfig,
    cfg: TSRCConfig,
    frame: Array,
    depth_map: Array,
    saliency_mask: Array,
    saliency_score: Array,
    pose: Array,
    t_now: Array,
    intr: geo.Intrinsics,
) -> Tuple[dcb.DCBuffer, TSRCStats]:
    """One TSRC update (paper Figure 3 (c), dark-gray steps 1-3).

    Args:
      buf: DC buffer state.
      frame: (H, W, 3) current frame F_t.
      depth_map: (H, W) predicted depth for F_t (for inserted entries).
      saliency_mask: (G*G,) bool S_t from HIR (SRD output).
      saliency_score: (G*G,) float saliency strength (stored with entries).
      pose: (4, 4) current camera pose U_t.
      t_now: scalar timestamp.

    Returns:
      Updated buffer and per-frame stats.
    """
    patch = buf.patch_size
    patches, origins = extract_patches(frame, patch)

    # --- TRD: warp buffered entries into the current view. ------------------
    # One analytic pose inversion, then a broadcast batch-multiply —
    # inv(U_t) is entry-independent, so inverting it N times under vmap
    # (the old formulation) was pure waste.
    t_rel = geo.relative_transform(buf.pose, pose)
    backend_fn = get_backend(cfg.backend)
    fused_match = getattr(backend_fn, "fused_match", None)
    n_patches = origins.shape[0]
    zero = jnp.zeros((), jnp.int32)
    if cfg.prefilter_k > 0 or cfg.patch_k > 0:
        # Two-phase sparse TRD (accelerator Section 4.1.1): corner-warp
        # bbox prefilter over all N entries, full reproject-match on the
        # K newest passing candidates only.  patch_k > 0 with
        # prefilter_k == 0 runs the same machinery with the candidate
        # budget at capacity (entry axis never truncates).
        k_entries = (
            min(cfg.prefilter_k, buf.capacity)
            if cfg.prefilter_k > 0
            else buf.capacity
        )
        pre = sparse_mod.bbox_prefilter(
            *dcb.entry_bbox_inputs(buf),
            t_rel,
            buf.t,
            buf.valid,
            origins,
            saliency_mask,
            intr,
            patch,
            o_min=cfg.o_min,
            k=k_entries,
        )
        idx = pre.cand_idx
        cand_valid = buf.valid[idx] & pre.cand_real
        if fused_match is not None:
            # Fused ∘ sparse composition: the fused kernel runs directly
            # on the gathered (K, ...) candidate slabs — warp + match +
            # thresholds + the per-(entry, patch) mask rows in one pass,
            # bitwise the scores "pallas" produces on the same slabs.
            _, _, _, c_pair, _ = fused_match(
                buf.rgb[idx],
                buf.depth[idx],
                buf.origin[idx],
                t_rel[idx],
                frame,
                intr,
                window=cfg.window,
                tau=cfg.tau,
                o_min=cfg.o_min,
                c_min=cfg.c_min,
            )
            pair_rows = c_pair & cand_valid[:, None]  # (K, M)
        else:
            c_diff, c_cov, _ = reproject_match(
                buf.rgb[idx],
                buf.depth[idx],
                buf.origin[idx],
                t_rel[idx],
                frame,
                intr,
                window=cfg.window,
                backend=cfg.backend,
            )
            entry_ok_c = (
                (c_diff <= cfg.tau) & (c_cov >= cfg.c_min) & cand_valid
            )
            pair_rows = entry_ok_c[:, None] & pre.overlap_ok[idx]  # (K, M)
        if 0 < cfg.patch_k < n_patches:
            # Patch-side sparsity: association on (K, P_k) compacted
            # slabs, matched/chosen scattered back to the dense grid
            # (non-selected patches report unmatched -> re-inserted).
            # P_k >= M would compact to an identity permutation — the
            # dense-M algebra below is the same result without the
            # top-P_k select, gather and scatter.
            pc = sparse_mod.compact_salient_patches(
                saliency_mask,
                pre.overlap_ok,
                pre.passes,
                k=min(cfg.patch_k, n_patches),
            )
            match_c = pair_rows[:, pc.idx] & pc.real[None, :]  # (K, P_k)
            idx_c, matched_c = dcb.newest_match(
                match_c, buf.t[idx], cand_valid
            )
            matched = (
                jnp.zeros((n_patches,), bool)
                .at[pc.idx]
                .set(matched_c & pc.real)
            )
            chosen = (
                jnp.zeros((n_patches,), jnp.int32)
                .at[pc.idx]
                .set(jnp.where(pc.real, idx[idx_c], 0))
            )
            n_patch_overflow = pc.n_overflow
            n_patch_checked = pc.n_compacted
        else:
            match_ok_c = pair_rows & saliency_mask[None, :]  # (K, M)
            idx_c, matched = dcb.newest_match(
                match_ok_c, buf.t[idx], cand_valid
            )
            chosen = idx[idx_c]
            n_patch_overflow = zero
            n_patch_checked = zero
        n_full_checks = pre.n_full
        n_overflow = pre.n_overflow
    elif fused_match is not None:
        # Capability-based dispatch: a backend may fuse warp + match +
        # occlusion/consistency thresholds + the per-(entry, patch)
        # update mask into one kernel (see reproject_match/fused.py).
        # New fused backends slot in here via registration alone — the
        # per-op dispatcher in kernels/reproject_match/ops.py and this
        # step body both stay untouched.
        diff, coverage, bbox, pair_ok, overlap_ok = fused_match(
            buf.rgb,
            buf.depth,
            buf.origin,
            t_rel,
            frame,
            intr,
            window=cfg.window,
            tau=cfg.tau,
            o_min=cfg.o_min,
            c_min=cfg.c_min,
        )
        match_ok = pair_ok & buf.valid[:, None] & saliency_mask[None, :]
        chosen, matched = dcb.newest_match(match_ok, buf.t, buf.valid)
        n_full_checks = None  # dense: derived from overlap_ok below
        n_overflow = zero
        n_patch_overflow = zero
        n_patch_checked = zero
    else:
        diff, coverage, bbox = reproject_match(
            buf.rgb,
            buf.depth,
            buf.origin,
            t_rel,
            frame,
            intr,
            window=cfg.window,
            backend=cfg.backend,
        )
        # --- Spatial association: warped-entry bbox vs patch grid. ---------
        overlap = geo.bbox_overlap_fraction(
            bbox[:, None, :], origins[None, :, :], patch
        )  # (N, M)
        overlap_ok = overlap >= cfg.o_min
        entry_ok = (diff <= cfg.tau) & (coverage >= cfg.c_min) & buf.valid
        match_ok = entry_ok[:, None] & overlap_ok & saliency_mask[None, :]
        chosen, matched = dcb.newest_match(match_ok, buf.t, buf.valid)
        n_full_checks = None  # dense: derived from overlap_ok below
        n_overflow = zero
        n_patch_overflow = zero
        n_patch_checked = zero
    # Snapshot the occupancy the TRD actually ran against: insertion
    # below permutes slots (top-k keep), so counters derived from the
    # post-insert mask would charge work against the wrong entries.
    valid_pre = buf.valid

    # --- Popularity bump for matches (step 3). ------------------------------
    buf = dcb.bump_popularity(buf, chosen, matched, t_now=t_now)

    # --- Insert unmatched salient patches. ----------------------------------
    insert_mask = saliency_mask & ~matched
    new = dcb.NewEntries(
        rgb=patches,
        depth=extract_depth_patches(depth_map, patch),
        pose=jnp.broadcast_to(pose, (patches.shape[0], 4, 4)),
        origin=origins,
        saliency=saliency_score,
    )
    buf = dcb.insert(buf, buf_cfg, new, insert_mask, t_now)

    if n_full_checks is None:
        # Dense paths: the ASIC would fully reproject only entries whose
        # bbox overlaps *some* salient patch (we computed densely; it
        # doesn't).  The sparse path reports its real candidate count —
        # when no truncation occurs the two numbers coincide exactly.
        any_overlap = jnp.any(overlap_ok & saliency_mask[None, :], axis=1)
        n_full_checks = jnp.sum((any_overlap & valid_pre).astype(jnp.int32))
    stats = TSRCStats(
        n_salient=jnp.sum(saliency_mask.astype(jnp.int32)),
        n_matched=jnp.sum(matched.astype(jnp.int32)),
        n_inserted=jnp.sum(insert_mask.astype(jnp.int32)),
        n_bbox_checks=jnp.sum(valid_pre.astype(jnp.int32)),
        n_full_checks=n_full_checks,
        buffer_valid=dcb.count_valid(buf),
        n_prefilter_overflow=n_overflow,
        n_patch_overflow=n_patch_overflow,
        n_patch_checked=n_patch_checked,
    )
    return buf, stats


def tsrc_step_sequential_oracle(
    buf: dcb.DCBuffer,
    buf_cfg: dcb.DCBufferConfig,
    cfg: TSRCConfig,
    frame: Array,
    depth_map: Array,
    saliency_mask: Array,
    saliency_score: Array,
    pose: Array,
    t_now: Array,
    intr: geo.Intrinsics,
):
    """Python-loop oracle of the ASIC's newest-first sequential scan.

    Used only in tests to prove the dense-parallel `newest_match` is
    equivalent to the paper's early-exit buffer walk.
    """
    import numpy as np

    patch = buf.patch_size
    patches, origins = extract_patches(frame, patch)
    t_rel = geo.relative_transform(buf.pose, pose)  # invert once
    diff, coverage, bbox = reproject_match(
        buf.rgb, buf.depth, buf.origin, t_rel, frame, intr,
        window=cfg.window, backend="ref",
    )
    overlap = np.asarray(
        geo.bbox_overlap_fraction(bbox[:, None, :], origins[None, :, :], patch)
    )
    diff = np.asarray(diff)
    coverage = np.asarray(coverage)
    valid = np.asarray(buf.valid)
    ts = np.asarray(buf.t)
    sal = np.asarray(saliency_mask)

    order = np.argsort(-ts)  # newest first, the ASIC walk order
    m = patches.shape[0]
    matched = np.zeros(m, bool)
    chosen = np.zeros(m, np.int32)
    for p in range(m):
        if not sal[p]:
            continue
        for c in order:
            if not valid[c]:
                continue
            if (
                diff[c] <= cfg.tau
                and coverage[c] >= cfg.c_min
                and overlap[c, p] >= cfg.o_min
            ):
                matched[p] = True
                chosen[p] = c
                break  # early exit at the first (newest) hit
    return chosen, matched
