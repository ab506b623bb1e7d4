"""All-pairs N-Body Pallas TPU kernel.

TPU adaptation of the CUDA sample's shared-memory tiling: CUDA stages source
bodies through shared memory tile-by-tile; here a block of target bodies
lives in VMEM across the inner grid dimension while source blocks stream in,
and the (block_j × block_i) interaction tile is evaluated as dense VPU math,
one 2-D temporary per coordinate.  Targets run across the 128 lanes (read
from the ``(4, n)`` transpose) and sources down the sublanes (read from the
``(n, 4)`` float4 layout), so no per-pair value needs a relayout.  The
j-loop is the innermost grid axis with a VMEM accumulator, mirroring the
GEMM pipeline structure — on TPU an interaction tile is bandwidth-free once
both blocks are resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv
from .ref import SOFTENING2


def _nbody_kernel(src_ref, tgt_ref, o_ref, acc_ref, *, j_steps: int,
                  softening2: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    src = src_ref[...]  # (bj, 4): xyz + mass of source bodies
    tgt = tgt_ref[...]  # (4, bi): xyz + mass of target bodies
    d = [src[:, c:c + 1] - tgt[c:c + 1, :] for c in range(3)]  # (bj, bi)
    dist2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2] + softening2
    inv_d = jax.lax.rsqrt(dist2)
    w = src[:, 3:4] * inv_d * inv_d * inv_d  # m_j / dist³
    for c in range(3):
        acc_ref[c:c + 1, :] += jnp.sum(w * d[c], axis=0, keepdims=True)

    @pl.when(j == j_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_j", "interpret", "softening2")
)
def nbody_pallas(
    posm: jax.Array,  # (n, 4) xyz+mass
    *,
    block_i: int = 512,
    block_j: int = 128,
    softening2: float = SOFTENING2,
    interpret: bool = False,
) -> jax.Array:
    n, four = posm.shape
    assert four == 4
    block_i = min(block_i, n)
    block_j = min(block_j, n)
    assert n % block_i == 0 and n % block_j == 0, "ops.py pads bodies"
    j_steps = cdiv(n, block_j)
    grid = (cdiv(n, block_i), j_steps)
    acc = pl.pallas_call(
        functools.partial(
            _nbody_kernel, j_steps=j_steps, softening2=softening2
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_j, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((4, block_i), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((3, block_i), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((3, n), posm.dtype),
        scratch_shapes=[pltpu.VMEM((3, block_i), jnp.float32)],
        interpret=interpret,
    )(posm, posm.T)
    return acc.T
