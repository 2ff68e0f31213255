"""StreamPool — batched and mesh-sharded multi-stream serving.

Wraps any :class:`~repro.api.compressor.Compressor` session over a
leading stream axis: one jitted ``vmap`` of ``step`` carries per-stream
state across chunk ingests.  This is the paper's datacenter deployment
mode — one accelerator ingesting many glasses streams in lock-step.

**Sharded serving mode**: pass a mesh (see
``repro.launch.mesh.make_stream_mesh``) and the pool ``shard_map``s the
same vmapped step over the mesh's stream axis — each device owns
``n_streams / axis_size`` sessions, with its shard of the carried state
donated in place.  The program is identical to the vmapped pool (a
1-device mesh is bit-identical to ``mesh=None``; a k-device mesh equals
k independent pools), so the pod-scale topology is purely a deployment
choice.

State buffers are donated to each ``step`` on accelerator backends, so
a pool holds exactly one copy of the per-stream carry in device memory
regardless of how many chunks it ingests.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.api.types import SensorChunk


class StreamPool:
    """A batch of ``n_streams`` independent compressor sessions.

    All pool methods take / return pytrees whose leaves carry a leading
    ``(n_streams, ...)`` axis; :meth:`step` expects the chunk's sensor
    arrays shaped ``(n_streams, T, ...)``.  Results are identical to
    running ``n_streams`` separate sessions (property-tested in
    ``tests/test_api.py`` / ``tests/test_stages.py``).

    Args:
      compressor: the session implementation to batch.
      n_streams: number of concurrent sessions.
      mesh: optional ``jax.sharding.Mesh`` — shards the stream axis over
        ``axis`` (pod-scale serving).  ``n_streams`` must divide evenly
        over the axis size.
      axis: mesh axis name to shard streams over (defaults to the
        mesh's first axis).
      donate: donate the carried state to each step (default: on for
        accelerator backends; CPU jax warns and ignores it).
    """

    def __init__(
        self,
        compressor,
        n_streams: int,
        *,
        mesh: Optional[Mesh] = None,
        axis: Optional[str] = None,
        donate: Optional[bool] = None,
    ):
        if getattr(compressor, "k_ladder", None) is not None:
            # The adaptive-K controller is host-driven (device_get +
            # Python rung state between chunks): the legacy lock-step
            # vmap of this pool genuinely cannot express per-stream
            # rungs — vmapping the host-driven step would die deep
            # inside the trace with a ConcretizationTypeError.  The
            # serving runtime CAN: it holds one controller per slot and
            # buckets slots by rung.
            raise ValueError(
                "StreamPool runs every stream in lock-step and cannot "
                "batch an adaptive-K compressor (k_ladder is host-side, "
                "per-session state); serve adaptive streams through "
                "repro.serve.StreamServer(ServerConfig(k_ladder=...)), "
                "which keeps per-stream rung state over a slotted pool"
            )
        self.compressor = compressor
        self.n_streams = n_streams
        self.mesh = mesh
        if donate is None:
            # Donation pays off (and is implemented) on accelerators;
            # CPU jax warns and ignores it.
            donate = jax.default_backend() != "cpu"
        vstep = jax.vmap(compressor.step)

        if mesh is not None:
            self.axis = axis if axis is not None else mesh.axis_names[0]
            if self.axis not in mesh.axis_names:
                raise ValueError(
                    f"axis {self.axis!r} not in mesh axes {mesh.axis_names}"
                )
            n_shards = mesh.shape[self.axis]
            if n_streams % n_shards != 0:
                raise ValueError(
                    f"n_streams={n_streams} must divide evenly over the "
                    f"{n_shards}-way {self.axis!r} mesh axis"
                )
            spec = PartitionSpec(self.axis)
            # Every leaf of (states, chunks) carries the stream axis in
            # front, so one prefix spec shards the whole step; each
            # device runs the vmapped step on its own shard.
            step = jax.shard_map(
                vstep,
                mesh=mesh,
                in_specs=(spec, spec),
                out_specs=(spec, spec),
                check_vma=False,
            )
            self._sharding = NamedSharding(mesh, spec)
        else:
            self.axis = None
            step = vstep
            self._sharding = None
        self._step = (
            jax.jit(step, donate_argnums=(0,)) if donate else jax.jit(step)
        )

    def init(self) -> Any:
        """Stacked fresh states: one session per stream (placed onto the
        mesh's stream-axis sharding in sharded mode)."""
        one = self.compressor.init()
        states = jax.tree.map(
            lambda x: jnp.repeat(x[None], self.n_streams, axis=0), one
        )
        if self._sharding is not None:
            states = jax.device_put(states, self._sharding)
        return states

    def step(self, states: Any, chunks: SensorChunk) -> Tuple[Any, Any]:
        """Ingest one chunk per stream; returns (states, stats), each
        with the leading stream axis."""
        if chunks.frames.ndim != 5 or chunks.frames.shape[0] != self.n_streams:
            raise ValueError(
                f"StreamPool({self.n_streams}) expects chunk arrays with a "
                f"leading stream axis, frames (n_streams, T, H, W, 3); got "
                f"frames shape {tuple(chunks.frames.shape)}"
            )
        return self._step(states, chunks)

    def export(self, states: Any):
        return jax.vmap(self.compressor.export)(states)

    def tokens(self, states: Any, seq_len: int):
        return jax.vmap(lambda s: self.compressor.tokens(s, seq_len))(states)
