"""Stage-graph pipeline tests: pre-refactor golden parity for EPIC and
all four baselines, stage registry + fail-fast validation, custom stage
pluggability, and the mesh-sharded StreamPool serving mode."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import hir
from repro.core import pipeline as P
from repro.core import tsrc as tsrc_mod
from repro.data import synthetic as SYN
from repro.launch.mesh import make_stream_mesh

FRAME = 64
PATCH = 16
N_FRAMES = 40
GOLDEN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "goldens",
    "stage_graph_golden.npz",
)

_SUB_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
for _k in ("JAX_PLATFORMS", "XLA_FLAGS", "HOME"):
    if _k in os.environ:
        _SUB_ENV[_k] = os.environ[_k]


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


@pytest.fixture(scope="module")
def chunk(golden):
    """The input chunk the goldens were computed from (stored with them,
    so a change of random-number generator cannot move the goldens)."""
    return api.SensorChunk(
        *(jnp.asarray(golden[f"input/{name}"])
          for name in ("frames", "poses", "gazes", "depth"))
    )


@pytest.fixture(scope="module")
def golden_hir_params(golden):
    """The HIR weights of the ``epic_hir`` golden, stored with it."""
    like = hir.init_params(jax.random.PRNGKey(7))
    n = len(jax.tree.leaves(like))
    return jax.tree.unflatten(
        jax.tree.structure(like),
        [jnp.asarray(golden[f"input/hir/{i}"]) for i in range(n)],
    )


def _ecfg(**kw):
    base = dict(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=32,
        tau=0.10, gamma=0.015, theta=8, window=16,
    )
    base.update(kw)
    return P.EPICConfig(**base)


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def _assert_matches_golden(golden, tag, state, stats):
    for i, leaf in enumerate(jax.tree.leaves(state)):
        np.testing.assert_array_equal(
            np.asarray(leaf), golden[f"{tag}/state/{i}"],
            err_msg=f"{tag}/state/{i}",
        )
    for i, leaf in enumerate(jax.tree.leaves(stats)):
        np.testing.assert_array_equal(
            np.asarray(leaf), golden[f"{tag}/stats/{i}"],
            err_msg=f"{tag}/stats/{i}",
        )


# ---------------------------------------------------------------------------
# Bit-identical to the pre-refactor monolithic pipeline (goldens captured
# before the stage-graph decomposition; see goldens/generate_stage_goldens.py)
# ---------------------------------------------------------------------------


class TestGoldenParity:
    def test_epic_oracle(self, chunk, golden):
        comp = api.get_compressor("epic")(_ecfg())
        state, stats = jax.jit(comp.step)(comp.init(), chunk)
        _assert_matches_golden(golden, "epic_oracle", state, stats)

    def test_epic_with_hir_model(self, chunk, golden, golden_hir_params):
        models = P.EPICModels(
            depth_params=None, hir_params=golden_hir_params
        )
        comp = api.get_compressor("epic")(_ecfg(), models)
        state, stats = jax.jit(comp.step)(comp.init(), chunk)
        _assert_matches_golden(golden, "epic_hir", state, stats)

    @pytest.mark.parametrize(
        "name,budget", [("fv", -1), ("sd", 64), ("td", 64), ("gc", 64)]
    )
    def test_baselines(self, name, budget, chunk, golden):
        comp = api.get_compressor(name)(api.BaselineConfig(
            frame_hw=(FRAME, FRAME), patch=PATCH,
            budget_patches=budget, n_frames=N_FRAMES,
        ))
        state, stats = jax.jit(comp.step)(comp.init(), chunk)
        _assert_matches_golden(golden, name, state, stats)


# ---------------------------------------------------------------------------
# Stage registry + graph plumbing
# ---------------------------------------------------------------------------


class TestStageRegistry:
    def test_builtin_stages_registered(self):
        assert set(api.available_stages()) >= {
            "bypass", "depth", "saliency", "tsrc",
            "select.fv", "select.sd", "select.td", "select.gc", "retain",
        }

    def test_unknown_stage_lists_available(self):
        with pytest.raises(KeyError, match="unknown frame stage"):
            api.make_stage("warp9000")
        with pytest.raises(KeyError, match="bypass"):
            api.get_stage("warp9000")

    def test_graph_state_layout_matches_epic_state(self):
        """The graph's carried state flattens to EPICState's leaves."""
        cfg = _ecfg()
        graph = P.build_epic_graph(cfg)
        gleaves = jax.tree.leaves(graph.init_state())
        sleaves = jax.tree.leaves(P.init_state(cfg))
        assert len(gleaves) == len(sleaves)
        for g, s in zip(gleaves, sleaves):
            assert g.shape == s.shape and g.dtype == s.dtype

    def test_pack_unpack_roundtrip(self):
        cfg = _ecfg()
        graph = P.build_epic_graph(cfg)
        state = P.init_state(cfg)
        packed = graph.pack_state(
            {"bypass": state.bypass, "tsrc": state.buf}, state.t
        )
        named, t = graph.unpack_state(packed)
        assert set(named) == {"bypass", "tsrc"}
        assert _tree_equal(named["bypass"], state.bypass)
        assert _tree_equal(named["tsrc"], state.buf)
        assert bool(jnp.array_equal(t, state.t))

    def test_pack_state_missing_stateful_stage_raises(self):
        graph = P.build_epic_graph(_ecfg())
        with pytest.raises(KeyError, match="tsrc"):
            graph.pack_state(
                {"bypass": P.init_state(_ecfg()).bypass},
                jnp.zeros(()),
            )

    def test_stage_names_walks_nested_graph(self):
        graph = P.build_epic_graph(_ecfg())
        assert graph.stage_names() == ("bypass", "depth", "saliency", "tsrc")

    def test_custom_stage_plugs_in(self, chunk):
        """A stage registered from user code composes into a graph with
        the built-ins — no scan-body edits anywhere."""

        @api.register_stage("test.half_gaze")
        class HalfGaze:
            name = "test.half_gaze"

            def init(self):
                return None

            def apply(self, state, ctx):
                return state, ctx._replace(gaze=ctx.gaze * 0.5)

        try:
            graph = api.StageGraph(
                [
                    api.make_stage("test.half_gaze"),
                    api.make_stage("select.gc", patch=PATCH, crop=32,
                                   frame_hw=(FRAME, FRAME)),
                    api.make_stage("retain", capacity=64, patch=PATCH),
                ],
                clock_init=lambda: jnp.zeros((), jnp.int32),
                clock_next=lambda t: t + 1,
            )
            gstate, stats = jax.jit(
                lambda gs: graph.scan(
                    gs, chunk.frames, chunk.poses, chunk.gazes, chunk.depth
                )
            )(graph.init_state())
            named, t = graph.unpack_state(gstate)
            rp, cursor = named["retain"]
            assert int(t) == N_FRAMES
            assert int(cursor) >= 0
            assert "retain" in stats
        finally:
            api.registry._STAGES.pop("test.half_gaze", None)


# ---------------------------------------------------------------------------
# Fail-fast backend / stage validation (satellite)
# ---------------------------------------------------------------------------


class TestFailFastValidation:
    def test_epic_config_unknown_backend(self):
        with pytest.raises(KeyError, match="unknown kernel backend"):
            P.EPICConfig(backend="cudnn")

    def test_epic_config_error_lists_registry_keys(self):
        with pytest.raises(KeyError, match="fused.*pallas.*ref"):
            P.EPICConfig(backend="nope")

    def test_tsrc_config_unknown_backend(self):
        with pytest.raises(KeyError, match="unknown kernel backend"):
            tsrc_mod.TSRCConfig(backend="nope")

    def test_replace_also_validates(self):
        """namedtuple._replace bypasses __new__; the configs must still
        fail fast on the idiomatic sweep path cfg._replace(backend=...)."""
        with pytest.raises(KeyError, match="unknown kernel backend"):
            P.EPICConfig()._replace(backend="typo")
        with pytest.raises(KeyError, match="unknown kernel backend"):
            tsrc_mod.TSRCConfig()._replace(backend="typo")
        assert P.EPICConfig()._replace(tau=0.2).tau == 0.2
        assert tsrc_mod.TSRCConfig()._replace(backend="fused").backend == (
            "fused"
        )

    def test_known_backends_construct(self):
        for backend in api.available_backends():
            assert P.EPICConfig(backend=backend).backend == backend


# ---------------------------------------------------------------------------
# Mesh-sharded StreamPool (satellite: 1-device mesh == vmapped pool ==
# N independent sessions; multi-device parity via subprocess)
# ---------------------------------------------------------------------------


class TestShardedPool:
    def _streams(self, n, n_frames=16):
        scfg = SYN.StreamConfig(n_frames=n_frames, hw=(FRAME, FRAME), n_obj=4)
        return [
            SYN.generate_stream(jax.random.PRNGKey(100 + i), scfg)[0]
            for i in range(n)
        ]

    def test_sharded_matches_vmapped_and_sessions(self):
        streams = self._streams(3)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *streams)
        bchunk = api.SensorChunk(
            batch.frames, batch.poses, batch.gazes, batch.depth
        )
        comp = api.EPICCompressor(_ecfg(capacity=16))

        vpool = api.StreamPool(comp, 3)
        vstates, vstats = vpool.step(vpool.init(), bchunk)

        mesh = make_stream_mesh()
        assert mesh.axis_names == ("streams",)
        spool = api.StreamPool(comp, 3, mesh=mesh)
        sstates, sstats = spool.step(spool.init(), bchunk)

        assert _tree_equal(sstates, vstates)
        assert _tree_equal(sstats, vstats)

        step = jax.jit(comp.step)
        for i, s in enumerate(streams):
            ref, _ = step(
                comp.init(),
                api.SensorChunk(s.frames, s.poses, s.gazes, s.depth),
            )
            assert _tree_equal(jax.tree.map(lambda x: x[i], sstates), ref)

    def test_sharded_multi_chunk_carry(self):
        streams = self._streams(2)
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *streams)
        comp = api.EPICCompressor(_ecfg(capacity=16))
        mesh = make_stream_mesh()

        spool = api.StreamPool(comp, 2, mesh=mesh)
        states = spool.init()
        for start in (0, 8):
            states, _ = spool.step(
                states,
                api.SensorChunk(
                    batch.frames[:, start:start + 8],
                    batch.poses[:, start:start + 8],
                    batch.gazes[:, start:start + 8],
                    batch.depth[:, start:start + 8],
                ),
            )
        vpool = api.StreamPool(comp, 2)
        vstates = vpool.init()
        for start in (0, 8):
            vstates, _ = vpool.step(
                vstates,
                api.SensorChunk(
                    batch.frames[:, start:start + 8],
                    batch.poses[:, start:start + 8],
                    batch.gazes[:, start:start + 8],
                    batch.depth[:, start:start + 8],
                ),
            )
        assert _tree_equal(states, vstates)

    def test_n_streams_must_divide_axis(self):
        comp = api.EPICCompressor(_ecfg(capacity=16))
        mesh = make_stream_mesh()
        n = mesh.shape["streams"]
        if n == 1:
            # every n_streams divides a 1-device axis; the 2-device
            # subprocess test below exercises the rejection path
            pytest.skip("needs a multi-device mesh")
        with pytest.raises(ValueError, match="divide evenly"):
            api.StreamPool(comp, n + 1, mesh=mesh)

    def test_unknown_axis_raises(self):
        comp = api.EPICCompressor(_ecfg(capacity=16))
        mesh = make_stream_mesh()
        with pytest.raises(ValueError, match="not in mesh axes"):
            api.StreamPool(comp, 2, mesh=mesh, axis="model")

    def test_two_device_shard_matches_vmap(self):
        """Real 2-shard run (forced host devices) == vmapped pool."""
        prog = """
import jax, jax.numpy as jnp, numpy as np
from repro import api
from repro.core import pipeline as P
from repro.data import synthetic as SYN
from repro.launch.mesh import make_stream_mesh

assert len(jax.devices()) == 2, jax.devices()
scfg = SYN.StreamConfig(n_frames=10, hw=(64, 64), n_obj=3)
streams = [SYN.generate_stream(jax.random.PRNGKey(i), scfg)[0]
           for i in range(4)]
batch = jax.tree.map(lambda *xs: jnp.stack(xs), *streams)
chunk = api.SensorChunk(batch.frames, batch.poses, batch.gazes, batch.depth)
cfg = P.EPICConfig(frame_hw=(64, 64), patch=16, capacity=12,
                   tau=0.10, gamma=0.015, theta=8, window=16)
comp = api.EPICCompressor(cfg)
vpool = api.StreamPool(comp, 4, donate=False)
vs, vt = vpool.step(vpool.init(), chunk)
spool = api.StreamPool(comp, 4, mesh=make_stream_mesh(), donate=False)
ss, st = spool.step(spool.init(), chunk)
for a, b in zip(jax.tree.leaves((vs, vt)), jax.tree.leaves((ss, st))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
try:
    api.StreamPool(comp, 3, mesh=make_stream_mesh())
except ValueError as e:
    assert "divide evenly" in str(e), e
else:
    raise AssertionError("expected divisibility ValueError")
print("SHARDED_OK")
"""
        env = dict(_SUB_ENV)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2"
        ).strip()
        r = subprocess.run(
            [sys.executable, "-c", prog],
            capture_output=True, text=True, timeout=500, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "SHARDED_OK" in r.stdout
