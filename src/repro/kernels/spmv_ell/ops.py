"""Public wrapper for the ELL SpMV kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import interpret_default, round_up
from .kernel import spmv_ell_pallas
from .ref import spmv_ell_ref


def spmv_ell(
    data: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    *,
    block: int = 8192,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """y = A @ x for A in ELL format (padded entries must have data == 0)."""
    if use_ref:
        return spmv_ell_ref(data, cols, x)
    interpret = interpret_default() if interpret is None else interpret
    rows, max_nnz = data.shape
    blk = min(block, rows)
    target = round_up(rows, blk)
    if target != rows:
        pad = target - rows
        data = jnp.concatenate([data, jnp.zeros((pad, max_nnz), data.dtype)])
        cols = jnp.concatenate([cols, jnp.zeros((pad, max_nnz), cols.dtype)])
    y = spmv_ell_pallas(data, cols, x, block=blk, interpret=interpret)
    return y[:rows]
