"""Production mesh + TPU v5e hardware constants.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — only the dry-run sets
``xla_force_host_platform_device_count=512`` before first jax init.

Mesh topology:
  single-pod: (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

``pod`` is an outer pure-DP axis: gradients all-reduce across pods over
DCN, weights are never sharded across pods (except the huge-MoE expert
axis, where EP spans (pod, data, model) — see sharding.py) — matching real
deployments where inter-pod bandwidth is an order of magnitude below ICI.
"""

from __future__ import annotations

from typing import Tuple

import jax

# --- TPU v5e per-chip constants (assignment-specified) ---------------------
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
DCN_BW = 6.25e9  # B/s per host pair (multi-pod axis); 25GB/s NIC /4 (est.)
HBM_BYTES = 16 * 1024**3  # 16 GiB


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0, (n, model_axis)
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_stream_mesh(n_devices: int = 0) -> jax.sharding.Mesh:
    """1-D mesh over the ``streams`` axis for sharded ``StreamPool``
    serving (pod-scale multi-stream ingest).

    ``n_devices=0`` uses every available device; a 1-device mesh is
    valid (and bit-identical to the vmapped pool), so the same serving
    code runs unchanged from a CPU laptop to a pod slice.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    return jax.sharding.Mesh(devs[:n], ("streams",))


def mesh_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def dp_axes(mesh: jax.sharding.Mesh):
    """The data-parallel axes: ('pod','data') when multi-pod else ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
