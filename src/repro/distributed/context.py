"""Ambient mesh context.

Model code that needs *manual* SPMD regions (``shard_map`` for MoE
dispatch and for the ReCXL replication engine) discovers the active mesh
through this context instead of threading it through every call. When no
context is set (CPU unit tests), modules fall back to their pure-local
single-shard path -- same math, no collectives.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

import jax
from jax.sharding import AxisType


@dataclass(frozen=True)
class MeshContext:
    mesh: jax.sharding.Mesh
    batch_axes: Tuple[str, ...]      # axes the batch is sharded over
    model_axis: Optional[str]        # tensor/expert-parallel axis
    fsdp_axes: Tuple[str, ...]       # axes parameters are fully sharded over

    @property
    def data_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def model_size(self) -> int:
        if self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


_CURRENT: Optional[MeshContext] = None


def set_mesh_context(ctx: Optional[MeshContext]) -> None:
    global _CURRENT
    _CURRENT = ctx


def get_mesh_context() -> Optional[MeshContext]:
    return _CURRENT


@contextlib.contextmanager
def mesh_context(ctx: MeshContext) -> Iterator[MeshContext]:
    prev = get_mesh_context()
    set_mesh_context(ctx)
    try:
        yield ctx
    finally:
        set_mesh_context(prev)


def make_mesh(axis_shapes: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``, the axis type the
    ``shard_map`` regions and the sharded jits expect."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         **kwargs)


def shard_map(f, mesh: jax.sharding.Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the
    callers' bodies are lane-wise or issue their collectives by hand."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=8)
def cells_mesh(n_shards: int) -> jax.sharding.Mesh:
    """1-D mesh over the first ``n_shards`` local devices, axis ``cells``.

    The protocol simulator's streaming tier shards the *cell* (grid
    batch) axis of its time-major ``(n_stores, B)`` tiles over it --
    each device scans its own slice of cells with zero cross-device
    communication. Cached per shard count: tiles of every signature
    share one mesh, so ``jit`` cache keys stay stable across tiles.
    """
    if not 1 <= n_shards <= len(jax.devices()):
        raise ValueError(
            f"n_shards must be in [1, {len(jax.devices())}], got {n_shards}")
    return make_mesh((n_shards,), ("cells",),
                     devices=jax.devices()[:n_shards])


def make_context(mesh: jax.sharding.Mesh) -> MeshContext:
    """Derive the canonical context from a mesh's axis names."""
    names = mesh.axis_names
    batch_axes = tuple(a for a in names if a in ("pod", "data"))
    model_axis = "model" if "model" in names else None
    return MeshContext(mesh=mesh, batch_axes=batch_axes,
                       model_axis=model_axis, fsdp_axes=batch_axes)
