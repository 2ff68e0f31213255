"""Compile the main path for a described TPU v5e chip (no chip attached).

The reproject-match Pallas kernels and the EPIC step are compiled with
the TPU compiler at the chip-smoke widths: 1024x1024 frames, patch 16,
a 192-entry DC buffer, window 32.  A compile that passes here is not a
chip run; it catches what interpret mode cannot — block shapes off the
(8, 128) tiling, scalars read from vector memory, more VMEM than a
kernel may use.  The topology is described inside a fixture, so every
test worker collects the same tests and only the one running this file
loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.core import geometry as geo
from repro.core import pipeline as P
from repro.kernels.reproject_match.fused import reproject_match_fused
from repro.kernels.reproject_match.kernel import (
    reproject_match_pallas,
    reproject_match_pallas_tiled,
)

FRAME = 1024
PATCH = 16
N = 192
WINDOW = 32
CHUNK = 10

KERNELS = {
    "pallas": reproject_match_pallas,
    "pallas_tiled": reproject_match_pallas_tiled,
    "fused": reproject_match_fused,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _op_args(sharding):
    return (
        _spec((N, PATCH, PATCH, 3), sharding),
        _spec((N, PATCH, PATCH), sharding),
        _spec((N, 2), sharding),
        _spec((N, 4, 4), sharding),
        _spec((FRAME, FRAME, 3), sharding),
        geo.Intrinsics(*(_spec((), sharding) for _ in range(3))),
    )


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel = KERNELS[name]
    compiled = (
        jax.jit(lambda *a: kernel(*a, window=WINDOW, interpret=False))
        .lower(*_op_args(one_chip))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_a_frame_off_the_tile_grid(name, one_chip):
    """A 60x60 frame, narrower than one 128-lane tile and not a whole
    number of 8-row tiles: the band DMA and its rotation still compile."""
    n, p, hw, window = 5, 8, 60, 20
    args = (
        _spec((n, p, p, 3), one_chip),
        _spec((n, p, p), one_chip),
        _spec((n, 2), one_chip),
        _spec((n, 4, 4), one_chip),
        _spec((hw, hw, 3), one_chip),
        geo.Intrinsics(*(_spec((), one_chip) for _ in range(3))),
    )
    kernel = KERNELS[name]
    compiled = (
        jax.jit(lambda *a: kernel(*a, window=window, interpret=False))
        .lower(*args)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_vmapped_kernel_compiles_for_v5e(name, one_chip):
    """A pool of streams vmaps the kernel; the frames stay in HBM."""
    kernel = KERNELS[name]
    *per_stream, intr = _op_args(one_chip)
    batched = [_spec((2, *s.shape), one_chip) for s in per_stream]
    fn = jax.vmap(
        lambda *a: kernel(*a, window=WINDOW, interpret=False),
        in_axes=(0, 0, 0, 0, 0, None),
    )
    compiled = jax.jit(fn).lower(*batched, intr).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["ref", *sorted(KERNELS)])
def test_epic_step_compiles_for_v5e(backend, one_chip):
    """The whole EPIC step; each Pallas backend picks its compiled kernel
    from the lowering platform, not from the CPU JAX runs on."""
    cfg = P.EPICConfig(
        frame_hw=(FRAME, FRAME), patch=PATCH, capacity=N, window=WINDOW,
        backend=backend,
    )
    comp = api.get_compressor("epic")(cfg)
    state = jax.tree.map(
        lambda s: _spec(s.shape, one_chip, s.dtype), jax.eval_shape(comp.init)
    )
    chunk = api.SensorChunk(
        _spec((CHUNK, FRAME, FRAME, 3), one_chip),
        _spec((CHUNK, 4, 4), one_chip),
        _spec((CHUNK, 2), one_chip),
        _spec((CHUNK, FRAME, FRAME), one_chip),
    )
    compiled = jax.jit(comp.step).lower(state, chunk).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend in KERNELS)
