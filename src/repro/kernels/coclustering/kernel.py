"""Co-clustering cluster-sum Pallas TPU kernel.

The hot kernel of CGC's iteration is the segmented reduction
``CoCavg[r, c] += Z[i, j]`` for ``r = row_assign[i], c = col_assign[j]``.
The CUDA version uses atomics into global memory; TPUs have none, so the
reduction is reformulated as a double one-hot matmul per tile —
``R₁ᵀ (Z C₁)`` — which runs on the MXU and emits per-row-block partials that
the Lightning ``reduce(+)`` annotation combines across devices.

``Z`` is tiled in both dimensions, so a tile's VMEM footprint does not grow
with the row width; the column-block axis is the inner (accumulation) grid
axis.  Both one-hots are built in-kernel from lane-dense ``(1, n)`` /
``(1, m)`` assignment rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv


def _csums_kernel(z_ref, ra_ref, ca_ref, out_ref, *, nrow_clusters: int,
                  ncol_clusters: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...]  # (block_n, block_m)
    ra = ra_ref[...]  # (1, block_n)
    ca = ca_ref[...]  # (1, block_m)
    c1t = (jax.lax.broadcasted_iota(jnp.int32, (ncol_clusters, ca.shape[1]), 0)
           == ca).astype(z.dtype)  # (C, block_m)
    r1t = (jax.lax.broadcasted_iota(jnp.int32, (nrow_clusters, ra.shape[1]), 0)
           == ra).astype(z.dtype)  # (R, block_n)
    zc = jax.lax.dot_general(  # Z C₁: (block_n, C)
        z, c1t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    out_ref[...] += jnp.dot(r1t, zc, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("nrow_clusters", "ncol_clusters", "block_n", "block_m",
                     "interpret"),
)
def cluster_sums_pallas(
    z: jax.Array,  # (n, m)
    row_assign: jax.Array,  # (n,)
    col_assign: jax.Array,  # (m,)
    *,
    nrow_clusters: int,
    ncol_clusters: int,
    block_n: int = 512,
    block_m: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    n, m = z.shape
    block_n = min(block_n, n)
    block_m = min(block_m, m)
    assert n % block_n == 0 and m % block_m == 0, "ops.py pads z"
    blocks = cdiv(n, block_n)
    partials = pl.pallas_call(
        functools.partial(_csums_kernel, nrow_clusters=nrow_clusters,
                          ncol_clusters=ncol_clusters),
        grid=(blocks, cdiv(m, block_m)),
        in_specs=[
            pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, nrow_clusters, ncol_clusters),
                               lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (blocks, nrow_clusters, ncol_clusters), jnp.float32),
        interpret=interpret,
    )(z, row_assign.astype(jnp.int32)[None, :],
      col_assign.astype(jnp.int32)[None, :])
    return partials.sum(axis=0)
