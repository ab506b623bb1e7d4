"""K-Means assignment + partial-reduction Pallas TPU kernel.

TPU adaptation of Rodinia's CUDA K-Means: the distance computation is
reformulated as a matmul (``|p - c|² = |p|² - 2 p·cᵀ + |c|²``) so the MXU
does the heavy lifting, and the per-block partial sums use a one-hot matmul
(again MXU) instead of CUDA's shared-memory atomics — TPUs have no atomics,
so the reduce(+) semantics of the annotation is realized as
partials-then-tree exactly like Lightning's planner does.

Points enter feature-major, ``(f, n)``: with few features a point-major
block would pad its ``f`` lanes to 128, while here points fill the lanes and
the transpose of a narrow ``(n, f)`` array is the layout XLA already keeps
it in.  Outputs are *per-block partials*: sums (blocks, k, f) and counts
(blocks, k, 1).  The caller (ops/launch) reduces over the leading axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv


def _kmeans_kernel(pt_ref, c_ref, sums_ref, counts_ref):
    pt = pt_ref[...]  # (f, block)
    c = c_ref[...]  # (k, f)
    k = c.shape[0]
    d2 = (
        jnp.sum(pt * pt, axis=0, keepdims=True)
        - 2.0 * jnp.dot(c, pt, preferred_element_type=jnp.float32)
        + jnp.sum(c * c, axis=1, keepdims=True)
    )  # (k, block)
    # argmin over centroids (first index of the minimum, like jnp.argmin).
    rows = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    best = jnp.min(d2, axis=0, keepdims=True)
    assign = jnp.min(jnp.where(d2 == best, rows, k), axis=0, keepdims=True)
    onehot = (rows == assign).astype(pt.dtype)  # (k, block)
    sums_ref[...] = jax.lax.dot_general(
        onehot, pt, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(sums_ref.dtype)
    counts_ref[...] = jnp.sum(onehot, axis=1, keepdims=True).astype(
        counts_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def kmeans_pallas(
    points: jax.Array,  # (n, f)
    centroids: jax.Array,  # (k, f)
    *,
    block: int = 8192,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    n, f = points.shape
    k, f2 = centroids.shape
    assert f == f2
    block = min(block, n)
    assert n % block == 0, "ops.py pads points"
    blocks = cdiv(n, block)
    sums, counts = pl.pallas_call(
        _kmeans_kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((f, block), lambda i: (0, i)),
            pl.BlockSpec((k, f), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, k, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, k, 1), lambda i: (i, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((blocks, k, f), jnp.float32),
            jax.ShapeDtypeStruct((blocks, k, 1), jnp.float32),
        ),
        interpret=interpret,
    )(points.T, centroids)
    return sums, counts[..., 0]
