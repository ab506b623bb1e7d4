"""Public wrapper for the co-clustering cluster-sum kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import interpret_default, round_up
from .kernel import cluster_sums_pallas
from .ref import cluster_sums_ref


def cluster_sums(
    z: jax.Array,
    row_assign: jax.Array,
    col_assign: jax.Array,
    nrow_clusters: int,
    ncol_clusters: int,
    *,
    block_n: int = 512,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    if use_ref:
        return cluster_sums_ref(
            z, row_assign, col_assign, nrow_clusters, ncol_clusters
        )
    interpret = interpret_default() if interpret is None else interpret
    n, m = z.shape
    bn, bm = min(block_n, n), min(1024, m)  # rows, columns per tile
    n_pad, m_pad = round_up(n, bn) - n, round_up(m, bm) - m
    if n_pad or m_pad:
        # Pad entries are zero, so whichever cluster they land in (0) gains
        # nothing.
        z = jnp.pad(z, ((0, n_pad), (0, m_pad)))
        row_assign = jnp.pad(row_assign, (0, n_pad))
        col_assign = jnp.pad(col_assign, (0, m_pad))
    return cluster_sums_pallas(
        z, row_assign, col_assign,
        nrow_clusters=nrow_clusters, ncol_clusters=ncol_clusters,
        block_n=bn, block_m=bm, interpret=interpret,
    )
