"""Distributed multi-dimensional arrays (paper §2.2) on ``jax.Array``.

A :class:`DistributedArray` pairs a ``jax.Array`` with a chunk
:class:`~repro.core.distributions.Distribution`.  On a named mesh the storage
layout is a ``NamedSharding`` derived from the distribution's partition spec;
on a single device it is an ordinary array, and the chunk structure exists
only in planner metadata (exactly the paper's "distributions affect
performance, not correctness").
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .distributions import Distribution, ReplicatedDist
from .ndrange import Region
from .planner import ArrayMeta


def _dtype_size(dtype: Any) -> int:
    return jnp.dtype(dtype).itemsize


@dataclasses.dataclass
class DistributedArray:
    """A logically-global array with a chunk distribution."""

    name: str
    value: jax.Array
    dist: Distribution
    mesh: Mesh | None = None
    mesh_axes: tuple[str, ...] = ()

    # -- metadata ---------------------------------------------------------------

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * _dtype_size(self.dtype)

    def meta(self) -> ArrayMeta:
        return ArrayMeta(
            name=self.name,
            shape=self.shape,
            dtype_size=_dtype_size(self.dtype),
            dist=self.dist,
        )

    def partition_spec(self) -> P:
        if self.mesh is None or isinstance(self.dist, ReplicatedDist):
            return P()
        spec = self.dist.partition_spec(self.mesh_axes)
        # Pad to array rank.
        spec = tuple(spec) + (None,) * (len(self.shape) - len(spec))
        return P(*spec[: len(self.shape)])

    def sharding(self) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.partition_spec())

    def chunks(self, num_devices: int | None = None):
        nd = num_devices or (self.mesh.size if self.mesh is not None else 1)
        return self.dist.chunks(self.shape, nd)

    # -- data access --------------------------------------------------------------

    def to_numpy(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.value))

    def read_region(self, region: Region) -> np.ndarray:
        return self.to_numpy()[region.to_slices()]

    def replace_value(self, value: jax.Array) -> "DistributedArray":
        return dataclasses.replace(self, value=value)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"DistributedArray({self.name!r}, shape={self.shape}, "
            f"dtype={self.dtype}, dist={type(self.dist).__name__})"
        )


def make_array(
    name: str,
    value: jax.Array | np.ndarray,
    dist: Distribution,
    mesh: Mesh | None = None,
    mesh_axes: Sequence[str] = (),
) -> DistributedArray:
    """Place ``value`` according to ``dist``.  On a mesh, host data goes
    straight to its ``NamedSharding``: each device receives only its share,
    never the whole array first."""
    arr = DistributedArray(
        name=name,
        value=value,
        dist=dist,
        mesh=mesh,
        mesh_axes=tuple(mesh_axes),
    )
    if mesh is not None and mesh.size > 1:
        arr.value = jax.device_put(value, arr.sharding())
    else:
        arr.value = jnp.asarray(value)
    return arr


def full_array(
    name: str,
    shape: Sequence[int],
    fill: Any,
    dtype: Any,
    dist: Distribution,
    mesh: Mesh | None = None,
    mesh_axes: Sequence[str] = (),
) -> DistributedArray:
    """A constant-filled array created in place: on a mesh each device
    materializes only its own shard."""
    shape = tuple(int(d) for d in shape)
    arr = DistributedArray(
        name=name,
        value=jax.ShapeDtypeStruct(shape, dtype),
        dist=dist,
        mesh=mesh,
        mesh_axes=tuple(mesh_axes),
    )
    if mesh is not None and mesh.size > 1:
        build = jax.jit(jnp.full, static_argnums=(0, 2),
                        out_shardings=arr.sharding())
        arr.value = build(shape, fill, jnp.dtype(dtype))
    else:
        arr.value = jnp.full(shape, fill, dtype)
    return arr
