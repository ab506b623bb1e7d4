"""Blocked GEMM Pallas TPU kernel (paper's GEMM benchmark, §4.2).

TPU adaptation of the Volkov-style CUDA GEMM the paper uses: instead of
shared-memory tiles + register blocking, we tile for VMEM and feed the MXU
with 128-aligned blocks.  The K loop is the innermost grid dimension so the
f32 VMEM accumulator is revisited across K steps ("multiple-of-128" MXU
contraction per step); A/B tiles double-buffer automatically via the Pallas
pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # bf16 products are exact in the f32 accumulator, and Mosaic takes no
    # fp32 contract precision for bf16 operands: only f32 operands follow
    # the configured matmul precision.
    precision = None if a_ref.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        "pad inputs to block multiples (ops.py does this)"
    )
    k_steps = cdiv(k, block_k)
    grid = (cdiv(m, block_m), cdiv(n, block_n), k_steps)

    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b)
