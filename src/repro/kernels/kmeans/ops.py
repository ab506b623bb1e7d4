"""Public wrappers for the K-Means kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import interpret_default, round_up
from .kernel import kmeans_pallas
from .ref import kmeans_assign_reduce_ref, kmeans_iteration_ref


def kmeans_assign_reduce(
    points: jax.Array,
    centroids: jax.Array,
    *,
    block: int = 8192,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(sums (k,f), counts (k,)) — block partials reduced on-device."""
    if use_ref:
        return kmeans_assign_reduce_ref(points, centroids)
    interpret = interpret_default() if interpret is None else interpret
    n, f = points.shape
    blk = min(block, n)
    target = round_up(n, blk)
    if target != n:
        # Pad with a far-away sentinel that lands in cluster 0; subtract its
        # contribution afterwards.  Simpler: pad with copies of point 0 and
        # correct counts/sums by the pad count's assignment — instead we pad
        # with zeros and mask via a weight column trick below.
        pad = target - n
        points = jnp.concatenate([points, jnp.zeros((pad, f), points.dtype)])
        sums, counts = kmeans_pallas(
            points, centroids, block=blk, interpret=interpret
        )
        sums = sums.sum(axis=0)
        counts = counts.sum(axis=0)
        # Remove the padding contribution: pad points are all-zero, assigned
        # to the centroid nearest the origin; they add zero to sums but `pad`
        # to that centroid's count.
        d0 = jnp.sum(centroids * centroids, axis=1)
        j = jnp.argmin(d0)
        counts = counts.at[j].add(-float(pad))
        return sums, counts
    sums, counts = kmeans_pallas(points, centroids, block=blk,
                                 interpret=interpret)
    return sums.sum(axis=0), counts.sum(axis=0)


def kmeans_iteration(
    points: jax.Array,
    centroids: jax.Array,
    **kw,
) -> jax.Array:
    """One full K-Means iteration (assignment + centroid update)."""
    if kw.pop("use_ref", False):
        return kmeans_iteration_ref(points, centroids)
    sums, counts = kmeans_assign_reduce(points, centroids, **kw)
    counts = jnp.maximum(counts, 1.0)
    return (sums / counts[:, None]).astype(centroids.dtype)
