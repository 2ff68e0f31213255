"""Sparse TRD v2 test suite: patch-side sparsity, fused∘sparse
composition, and the adaptive-K controller.

Pins the PR-4 contracts on top of the PR-3 sparse TRD:

* ``compact_salient_patches`` selection semantics (composite
  (salient, has-passing-entry) key, newest-first entry parity trick
  mirrored onto the patch axis);
* **patch-compacted bitwise parity with the dense patch axis whenever
  at most P_k salient patches exist** — at the ``tsrc_step`` level, per
  backend, under jit, and through the chunked ``EPICCompressor``
  session (with a learned-saliency model so compaction is real);
* conservative ``n_patch_overflow`` truncation semantics;
* fused∘sparse: the fused kernel on gathered candidate slabs is
  bitwise the ``"pallas"`` backend's scores on the same slabs, and the
  whole step composes prefilter + fused bitwise with the dense path;
* adaptive-K: deterministic trajectory, never-moves == fixed-K bitwise,
  ladder fail-fast validation;
* ``patch_k`` fail-fast validation, graph-construction memoization, and
  the measured patch-compacted ``dc_traffic_bytes`` accounting (dense
  runs unchanged).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import dc_buffer as dcb
from repro.core import geometry as geo
from repro.core import hir
from repro.core import pipeline as P
from repro.core import tsrc as tsrc_mod
from repro.data import synthetic as SYN
from repro.kernels.reproject_match import sparse as sparse_mod
from repro.kernels.reproject_match.fused import reproject_match_fused
from repro.kernels.reproject_match.ops import reproject_match

FRAME = 64
PATCH = 16
N_PATCHES = (FRAME // PATCH) ** 2


def _intr(hw=FRAME):
    return geo.Intrinsics.create(0.8 * hw, hw / 2.0, hw / 2.0)


def _tree_equal_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# compact_salient_patches unit semantics
# ---------------------------------------------------------------------------


class TestCompactSalientPatches:
    def _compact(self, salient, has_entry_rows, k):
        n = has_entry_rows.shape[0]
        passes = jnp.ones((n,), bool)
        return sparse_mod.compact_salient_patches(
            salient, has_entry_rows, passes, k=k
        )

    def test_all_salient_selected_when_under_k(self):
        salient = jnp.array([True, False, True, False, True, False])
        overlap = jnp.zeros((3, 6), bool)
        pc = self._compact(salient, overlap, k=4)
        assert int(pc.n_salient) == 3
        assert int(pc.n_compacted) == 3
        assert int(pc.n_overflow) == 0
        chosen = set(np.asarray(pc.idx[pc.real]).tolist())
        assert chosen == {0, 2, 4}

    def test_matchable_salient_patches_win_slots_under_truncation(self):
        # 4 salient patches, only room for 2; entries overlap patches 3, 5.
        salient = jnp.array([True, True, False, True, False, True])
        overlap = jnp.zeros((2, 6), bool).at[0, 3].set(True).at[1, 5].set(
            True
        )
        pc = self._compact(salient, overlap, k=2)
        assert int(pc.n_overflow) == 2
        assert set(np.asarray(pc.idx).tolist()) == {3, 5}
        assert bool(jnp.all(pc.real))

    def test_nonsalient_fillers_marked_not_real(self):
        salient = jnp.zeros((6,), bool).at[2].set(True)
        pc = self._compact(salient, jnp.zeros((2, 6), bool), k=3)
        assert int(pc.n_compacted) == 1
        assert int(jnp.sum(pc.real.astype(jnp.int32))) == 1
        assert int(pc.idx[jnp.argmax(pc.real)]) == 2

    def test_overlap_from_nonpassing_entry_does_not_rank(self):
        salient = jnp.array([True, True, False, False])
        overlap = jnp.ones((1, 4), bool)
        passes = jnp.array([False])  # entry overlaps all but doesn't pass
        pc = sparse_mod.compact_salient_patches(
            salient, overlap, passes, k=1
        )
        # Both salient patches rank equally (no passing entry): the
        # lowest index wins the single slot.
        assert int(pc.idx[0]) == 0
        assert int(pc.n_overflow) == 1


# ---------------------------------------------------------------------------
# Patch-compacted step == dense patch axis (no truncation), per backend
# ---------------------------------------------------------------------------


class TestPatchCompactionParity:
    CAP = 32

    def _frames(self, seed=0):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        f1 = jax.random.uniform(k1, (FRAME, FRAME, 3))
        f2 = f1.at[:, FRAME // 2 :].set(
            jax.random.uniform(k2, (FRAME, FRAME // 2, 3))
        )
        return f1, f2

    def _run_steps(
        self, prefilter_k, patch_k, backend="ref", jit=False, n_sal=2
    ):
        buf_cfg = dcb.DCBufferConfig(capacity=self.CAP, patch=PATCH)
        cfg = tsrc_mod.TSRCConfig(
            window=32, backend=backend,
            prefilter_k=prefilter_k, patch_k=patch_k,
        )
        # Partial saliency so P_k < M compaction is actually exercised.
        sal = jnp.zeros((N_PATCHES,), bool).at[jnp.arange(n_sal)].set(True)
        common = (
            jnp.full((FRAME, FRAME), 3.0), sal, jnp.ones((N_PATCHES,)),
            jnp.eye(4),
        )
        step = tsrc_mod.tsrc_step
        if jit:
            step = jax.jit(step, static_argnames=("buf_cfg", "cfg"))
        f1, f2 = self._frames()
        buf = dcb.init(buf_cfg)
        buf, _ = step(
            buf, buf_cfg, cfg, f1, *common, jnp.float32(0), _intr()
        )
        buf, stats = step(
            buf, buf_cfg, cfg, f2, *common, jnp.float32(1), _intr()
        )
        return buf, stats

    @pytest.mark.parametrize("jit", [False, True])
    def test_compacted_bitwise_equals_dense_patch_axis(self, jit):
        """P_k >= n_salient never truncates: buffer and every shared
        counter equal the patch-dense sparse run bit for bit."""
        dense_p = self._run_steps(self.CAP, 0, jit=jit)
        comp_p = self._run_steps(self.CAP, 2, jit=jit)
        # State bitwise; stats equal except the two patch-compaction
        # observability leaves.
        _tree_equal_bitwise(dense_p[0], comp_p[0])
        _tree_equal_bitwise(
            dense_p[1]._replace(n_patch_checked=jnp.int32(0)),
            comp_p[1]._replace(n_patch_checked=jnp.int32(0)),
        )
        assert int(comp_p[1].n_patch_overflow) == 0
        assert int(comp_p[1].n_patch_checked) == 2
        assert int(dense_p[1].n_patch_checked) == 0

    def test_compacted_bitwise_equals_fully_dense(self):
        """Both-axis sparsity (entry top-K at capacity + patch top-P_k
        over the salient count) == the fully dense step, bit for bit."""
        dense = self._run_steps(0, 0, n_sal=3)
        both = self._run_steps(self.CAP, 3, n_sal=3)
        _tree_equal_bitwise(
            dense[0], both[0]
        )
        _tree_equal_bitwise(
            dense[1]._replace(n_patch_checked=jnp.int32(0)),
            both[1]._replace(n_patch_checked=jnp.int32(0)),
        )

    @pytest.mark.parametrize("backend", ["pallas", "pallas_tiled", "fused"])
    def test_parity_on_every_backend(self, backend):
        dense = self._run_steps(0, 0, backend="ref")
        comp_p = self._run_steps(self.CAP, 2, backend=backend)
        _tree_equal_bitwise(dense[0], comp_p[0])
        assert int(comp_p[1].n_patch_overflow) == 0

    def test_patch_k_at_least_m_is_identity(self):
        """P_k >= M skips compaction entirely (identity permutation):
        bitwise the patch-dense path including the zero counters."""
        a = self._run_steps(self.CAP, 0)
        b = self._run_steps(self.CAP, N_PATCHES)
        c = self._run_steps(self.CAP, N_PATCHES + 7)
        _tree_equal_bitwise(a, b)
        _tree_equal_bitwise(a, c)
        assert int(b[1].n_patch_checked) == 0

    def test_patch_truncation_is_conservative(self):
        """P_k < n_salient drops salient patches from the match algebra
        only: extra insertions, never false matches; overflow counted."""
        dense_p, dense_stats = self._run_steps(self.CAP, 0, n_sal=4)
        _, trunc_stats = self._run_steps(self.CAP, 1, n_sal=4)
        assert int(trunc_stats.n_patch_overflow) == 3
        assert int(trunc_stats.n_patch_checked) == 1
        assert int(trunc_stats.n_matched) <= int(dense_stats.n_matched)
        assert int(trunc_stats.n_inserted) >= int(dense_stats.n_inserted)
        assert int(trunc_stats.n_matched) + int(trunc_stats.n_inserted) == (
            int(trunc_stats.n_salient)
        )

    def test_patch_only_sparsity_without_prefilter(self):
        """patch_k > 0 with prefilter_k == 0 runs the sparse machinery
        with the candidate budget at capacity — bitwise dense, zero
        entry overflow."""
        dense = self._run_steps(0, 0)
        ponly = self._run_steps(0, 2)
        _tree_equal_bitwise(dense[0], ponly[0])
        assert int(ponly[1].n_prefilter_overflow) == 0


# ---------------------------------------------------------------------------
# Fused ∘ sparse composition
# ---------------------------------------------------------------------------


class TestFusedSparseComposition:
    CAP = 32
    K = 8

    def _slabs(self, seed=3):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        rgb = jax.random.uniform(k1, (self.K, PATCH, PATCH, 3))
        dep = jax.random.uniform(k2, (self.K, PATCH, PATCH)) * 2 + 1.0
        orig = jax.random.uniform(k3, (self.K, 2)) * (FRAME - PATCH)
        t_rel = jnp.broadcast_to(jnp.eye(4), (self.K, 4, 4))
        frame = jax.random.uniform(k1, (FRAME, FRAME, 3))
        return rgb, dep, orig, t_rel, frame

    def test_fused_scores_bitwise_pallas_on_candidate_slabs(self):
        """The fused kernel's (diff, coverage, bbox) on a gathered
        candidate slab are bitwise the "pallas" backend's on the same
        slab, and its mask rows are exactly the thresholded scores."""
        rgb, dep, orig, t_rel, frame = self._slabs()
        tau, o_min, c_min, window = 0.1, 0.5, 0.6, 32
        d_f, c_f, b_f, pair, ovok = reproject_match_fused(
            rgb, dep, orig, t_rel, frame, _intr(),
            window=window, tau=tau, o_min=o_min, c_min=c_min,
            interpret=True,
        )
        d_p, c_p, b_p = reproject_match(
            rgb, dep, orig, t_rel, frame, _intr(),
            window=window, backend="pallas",
        )
        _tree_equal_bitwise((d_f, c_f, b_f), (d_p, c_p, b_p))
        # Mask rows == thresholds applied to those very scores.
        _, origins = tsrc_mod.extract_patches(
            jnp.zeros((FRAME, FRAME, 3)), PATCH
        )
        overlap = geo.bbox_overlap_fraction(
            b_p[:, None, :], origins[None, :, :], PATCH
        )
        np.testing.assert_array_equal(
            np.asarray(ovok), np.asarray(overlap >= o_min)
        )
        entry_ok = (d_p <= tau) & (c_p >= c_min)
        np.testing.assert_array_equal(
            np.asarray(pair), np.asarray(entry_ok[:, None] & ovok)
        )

    @pytest.mark.parametrize("patch_k", [0, 2])
    def test_step_fused_sparse_bitwise_vs_pallas_sparse(self, patch_k):
        """tsrc_step with backend="fused" + prefilter no longer falls
        back: whole step bitwise vs the "pallas" sparse path."""
        h = TestPatchCompactionParity()
        a = h._run_steps(self.CAP, patch_k, backend="pallas")
        b = h._run_steps(self.CAP, patch_k, backend="fused")
        _tree_equal_bitwise(a, b)

    def test_step_fused_sparse_bitwise_vs_dense(self):
        h = TestPatchCompactionParity()
        dense = h._run_steps(0, 0, backend="ref")
        fused = h._run_steps(self.CAP, 2, backend="fused")
        _tree_equal_bitwise(dense[0], fused[0])


# ---------------------------------------------------------------------------
# Chunked-session parity with a learned saliency model (real compaction)
# ---------------------------------------------------------------------------


class TestSessionPatchSparsity:
    @pytest.fixture(scope="class")
    def stream(self):
        scfg = SYN.StreamConfig(n_frames=24, hw=(FRAME, FRAME), n_obj=4)
        s, _ = SYN.generate_stream(jax.random.PRNGKey(2), scfg)
        return api.SensorChunk(s.frames, s.poses, s.gazes, s.depth)

    @pytest.fixture(scope="class")
    def models(self, stream):
        """HIR with its head bias centred at the stream's median logit,
        so per-frame saliency is genuinely partial (random init tends to
        saturate the binary threshold all-or-nothing)."""
        from repro.core import depth as depth_mod

        params = hir.init_params(jax.random.PRNGKey(7))
        grid = FRAME // PATCH
        rgb64 = jax.vmap(
            lambda f: depth_mod.resize_image(f, hir.HIR_INPUT)
        )(stream.frames)
        heat = jax.vmap(
            lambda g: hir.gaze_heatmap(g, hir.HIR_INPUT, (FRAME, FRAME))
        )(stream.gazes)
        logits = hir.forward(params, rgb64, heat, grid)
        params = dict(params)
        params["b3"] = params["b3"] - jnp.median(logits)
        return P.EPICModels(depth_params=None, hir_params=params)

    def _cfg(self, prefilter_k=0, patch_k=0):
        return P.EPICConfig(
            frame_hw=(FRAME, FRAME), patch=PATCH, capacity=48,
            tau=0.10, gamma=0.015, theta=8, window=16,
            prefilter_k=prefilter_k, patch_k=patch_k,
        )

    def test_session_bitwise_with_real_compaction(self, stream, models):
        """With HIR saliency the per-frame salient count is < M: pick
        P_k at the observed peak so compaction is real yet exact — the
        full chunked session equals dense bit for bit."""
        dense = api.EPICCompressor(self._cfg(), models)
        ds, dt = jax.jit(dense.step)(dense.init(), stream)
        peak_sal = int(jnp.max(dt.n_salient))
        assert 0 < peak_sal < N_PATCHES, "seed must give partial saliency"
        comp = api.EPICCompressor(self._cfg(48, peak_sal), models)
        ss, st = jax.jit(comp.step)(comp.init(), stream)
        _tree_equal_bitwise(ds, ss)
        assert int(jnp.sum(st.n_patch_overflow)) == 0
        # Compaction really ran on processed frames.
        assert int(jnp.max(st.n_patch_checked)) == peak_sal
        _tree_equal_bitwise(
            dt._replace(n_patch_checked=jnp.zeros_like(dt.n_patch_checked)),
            st._replace(n_patch_checked=jnp.zeros_like(st.n_patch_checked)),
        )

    def test_chunked_ingest_bitwise_equals_one_shot(self, stream, models):
        comp = api.EPICCompressor(self._cfg(48, 4), models)
        one_state, _ = jax.jit(comp.step)(comp.init(), stream)
        step = jax.jit(comp.step)
        state = comp.init()
        for lo, hi in ((0, 8), (8, 16), (16, 24)):
            state, _ = step(
                state,
                api.SensorChunk(
                    stream.frames[lo:hi], stream.poses[lo:hi],
                    stream.gazes[lo:hi], stream.depth[lo:hi],
                ),
            )
        _tree_equal_bitwise(one_state, state)

    def test_dc_traffic_charges_measured_patch_reads(self, stream, models):
        """Dense runs' dc_traffic_bytes are unchanged by the new leaf;
        patch-compacted runs add the measured n_full x n_patch_checked
        bbox-row reads."""
        from repro.core import retained as ret

        cfg_d = self._cfg(48, 0)
        dense = api.EPICCompressor(cfg_d, models)
        _, dt = jax.jit(dense.step)(dense.init(), stream)
        ctr_d = P.stream_counters(cfg_d, dt)
        expect_dense = (
            int(jnp.sum(dt.n_full_checks)) * ret.patch_rgb_bytes(PATCH)
            + int(jnp.sum(dt.n_inserted)) * ret.dc_entry_bytes(PATCH)
        )
        assert ctr_d.dc_traffic_bytes == expect_dense

        cfg_s = self._cfg(48, 4)
        comp = api.EPICCompressor(cfg_s, models)
        _, st = jax.jit(comp.step)(comp.init(), stream)
        ctr_s = P.stream_counters(cfg_s, st)
        pair_reads = int(jnp.sum(st.n_full_checks * st.n_patch_checked))
        expect_sparse = (
            int(jnp.sum(st.n_full_checks)) * ret.patch_rgb_bytes(PATCH)
            + int(jnp.sum(st.n_inserted)) * ret.dc_entry_bytes(PATCH)
            + pair_reads * ret.bbox_row_bytes()
        )
        assert pair_reads > 0
        assert ctr_s.dc_traffic_bytes == expect_sparse


# ---------------------------------------------------------------------------
# Adaptive-K controller
# ---------------------------------------------------------------------------


class TestAdaptiveK:
    LADDER = (4, 8, 16, 48)

    @pytest.fixture(scope="class")
    def stream(self):
        scfg = SYN.StreamConfig(n_frames=32, hw=(FRAME, FRAME), n_obj=4)
        s, _ = SYN.generate_stream(jax.random.PRNGKey(5), scfg)
        return s

    def _cfg(self, prefilter_k=4):
        return P.EPICConfig(
            frame_hw=(FRAME, FRAME), patch=PATCH, capacity=48,
            tau=0.10, gamma=0.015, theta=8, window=16,
            prefilter_k=prefilter_k,
        )

    def _chunks(self, s, n=8):
        for lo in range(0, s.frames.shape[0], n):
            yield api.SensorChunk(
                s.frames[lo:lo + n], s.poses[lo:lo + n],
                s.gazes[lo:lo + n], s.depth[lo:lo + n],
            )

    def _run(self, s, **kw):
        comp = api.EPICCompressor(self._cfg(), k_ladder=self.LADDER, **kw)
        state = comp.init()
        for c in self._chunks(s):
            state, _ = comp.step(state, c)
        return comp, state

    def test_trajectory_deterministic(self, stream):
        c1, s1 = self._run(stream)
        c2, s2 = self._run(stream)
        assert c1.k_trajectory == c2.k_trajectory
        assert len(c1.k_trajectory) == 4
        _tree_equal_bitwise(s1, s2)
        # Rungs only move to adjacent ladder positions.
        pos = [self.LADDER.index(k) for k in c1.k_trajectory]
        assert all(abs(b - a) <= 1 for a, b in zip(pos, pos[1:]))

    def test_grows_on_overflow(self, stream):
        comp, _ = self._run(stream)
        # Starting at the bottom rung of a stream with >4 passing
        # entries per frame, the controller must climb.
        assert comp.k_trajectory[0] == 4
        assert comp.k_trajectory[-1] > 4

    def test_never_moves_is_bitwise_fixed_k(self, stream):
        fixed = api.EPICCompressor(self._cfg(48))
        step = jax.jit(fixed.step)
        fs = fixed.init()
        for c in self._chunks(stream):
            fs, _ = step(fs, c)
        adap = api.EPICCompressor(self._cfg(48), k_ladder=(48,))
        as_ = adap.init()
        for c in self._chunks(stream):
            as_, _ = adap.step(as_, c)
        assert adap.k_trajectory == [48] * 4
        _tree_equal_bitwise(fs, as_)

    def test_one_cached_step_per_visited_rung(self, stream):
        comp, _ = self._run(stream)
        assert set(comp._rung_steps) == set(comp.k_trajectory)

    def test_run_session_uses_host_step(self, stream):
        comp = api.EPICCompressor(self._cfg(), k_ladder=self.LADDER)
        chunk = api.SensorChunk(
            stream.frames, stream.poses, stream.gazes, stream.depth
        )
        state, _ = api.run_session(comp, chunk, chunk_size=8)
        assert len(comp.k_trajectory) == 4
        assert int(dcb.count_valid(state.buf)) > 0

    def test_ladder_validation(self):
        for bad in ((), (0, 4), (8, 8), (16, 8), ("a",)):
            with pytest.raises((ValueError, TypeError)):
                api.EPICCompressor(self._cfg(), k_ladder=bad)
        with pytest.raises(ValueError, match="not a rung"):
            api.EPICCompressor(self._cfg(5), k_ladder=(4, 8))
        # prefilter_k = 0 starts at the bottom rung.
        comp = api.EPICCompressor(self._cfg(0), k_ladder=(4, 8))
        assert comp.k_ladder == (4, 8)


# ---------------------------------------------------------------------------
# Fail-fast validation + graph memoization
# ---------------------------------------------------------------------------


class TestPatchKValidation:
    def test_negative_rejected(self):
        with pytest.raises(ValueError, match="patch_k"):
            tsrc_mod.TSRCConfig(patch_k=-1)
        with pytest.raises(ValueError, match="patch_k"):
            P.EPICConfig(patch_k=-3)

    def test_non_int_rejected(self):
        with pytest.raises(TypeError, match="patch_k"):
            tsrc_mod.TSRCConfig(patch_k=2.5)

    def test_replace_also_validates(self):
        with pytest.raises(ValueError, match="patch_k"):
            P.EPICConfig()._replace(patch_k=-2)
        assert P.EPICConfig()._replace(patch_k=8).patch_k == 8

    def test_zero_is_dense_default(self):
        assert tsrc_mod.TSRCConfig().patch_k == 0
        assert P.EPICConfig().patch_k == 0


class TestGraphMemoization:
    def test_same_cfg_and_models_hits_cache(self):
        cfg = P.EPICConfig(frame_hw=(FRAME, FRAME), patch=PATCH, capacity=8)
        models = P.EPICModels()
        g1 = P.build_epic_graph(cfg, models)
        g2 = P.build_epic_graph(cfg, models)
        assert g1 is g2

    def test_distinct_cfg_misses(self):
        models = P.EPICModels()
        g1 = P.build_epic_graph(
            P.EPICConfig(frame_hw=(FRAME, FRAME), patch=PATCH, capacity=8),
            models,
        )
        g2 = P.build_epic_graph(
            P.EPICConfig(frame_hw=(FRAME, FRAME), patch=PATCH, capacity=16),
            models,
        )
        assert g1 is not g2

    def test_distinct_models_identity_misses(self):
        cfg = P.EPICConfig(frame_hw=(FRAME, FRAME), patch=PATCH, capacity=8)
        g1 = P.build_epic_graph(cfg, P.EPICModels())
        g2 = P.build_epic_graph(cfg, P.EPICModels())
        assert g1 is not g2

    def test_eager_process_frame_reuses_graph(self):
        cfg = P.EPICConfig(frame_hw=(FRAME, FRAME), patch=PATCH, capacity=8)
        models = P.EPICModels()
        state = P.init_state(cfg)
        frame = jnp.zeros((FRAME, FRAME, 3))
        depth = jnp.ones((FRAME, FRAME))
        pose = jnp.eye(4)
        gaze = jnp.zeros((2,))
        before = P.build_epic_graph(cfg, models)
        s1, _ = P.process_frame(state, frame, pose, gaze, depth, models, cfg)
        s2, _ = P.process_frame(s1, frame, pose, gaze, depth, models, cfg)
        assert P.build_epic_graph(cfg, models) is before
        assert int(s2.t) == 2
