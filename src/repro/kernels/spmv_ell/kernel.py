"""SpMV (ELL) Pallas TPU kernel.

TPU adaptation of SHOC's CUDA ELLPACK SpMV.  CUDA gathers ``x[cols]`` per
thread from global memory inside the kernel.  Mosaic lowers only gathers
within one vector tile, and the paper's replicated ``x`` (64 MiB at 2^24
rows) does not fit VMEM, so the gather of ``x`` runs as an XLA gather from
HBM ahead of the kernel.  The kernel takes the ELL slots column-major —
``(max_nnz, rows)``, the ELLPACK-R layout SHOC's CUDA kernel reads, so rows
fill the 128 lanes — and fuses the products with the per-row reduction.
Padded entries carry ``data == 0`` so no mask is needed in the inner loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import cdiv


def _spmv_kernel(data_ref, xg_ref, y_ref):
    # (max_nnz, block) slots × gathered x → (1, block) row sums.
    y_ref[...] = jnp.sum(data_ref[...] * xg_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def spmv_ell_pallas(
    data: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    *,
    block: int = 8192,
    interpret: bool = False,
) -> jax.Array:
    rows, max_nnz = data.shape
    block = min(block, rows)
    assert rows % block == 0, "ops.py pads rows"
    x_gathered = jnp.take(x, cols.T, axis=0, fill_value=0.0)
    spec = pl.BlockSpec((max_nnz, block), lambda i: (0, i))
    y = pl.pallas_call(
        _spmv_kernel,
        grid=(cdiv(rows, block),),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, rows), data.dtype),
        interpret=interpret,
    )(data.T, x_gathered)
    return y[0]
