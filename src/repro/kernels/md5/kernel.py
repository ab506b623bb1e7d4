"""MD5 key-search Pallas TPU kernel.

TPU adaptation of SHOC's CUDA MD5: CUDA runs one hash per thread with the 64
rounds unrolled in registers; on TPU the same 64 rounds run lane-wise on the
VPU over a ``(block // 128, 128)`` tile of keys held in VREGs.  All
operations are uint32 adds / ands / rotates — no MXU, no memory traffic
beyond the block index, making this the paper's pure-compute scaling
benchmark.

The target digest is scalar-prefetched into SMEM.  Each grid step emits the
block's min matching index as a ``(1, 1)`` block; the host (or the Lightning
``reduce(min)`` annotation on a mesh) reduces across blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANE, cdiv
from .ref import md5_u32x2


def _md5_kernel(tgt_ref, out_ref, *, block: int, total: int):
    i = pl.program_id(0)
    shape = (block // LANE, LANE)
    idx = (i * block
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANE
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    w0 = idx.astype(jnp.uint32)
    w1 = w0 ^ jnp.uint32(0x9E3779B9)
    a, b, c, d = md5_u32x2(w0, w1)
    tgt = [tgt_ref[j].astype(jnp.uint32) for j in range(4)]  # same bits
    hit = (a == tgt[0]) & (b == tgt[1]) & (c == tgt[2]) & (d == tgt[3])
    found = jnp.where(hit & (idx < total), idx, jnp.int32(total))
    out_ref[...] = jnp.min(found, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def md5_search_pallas(
    n: int,
    target: jax.Array,  # (4,) uint32
    *,
    block: int = 8 * 128 * 8,
    interpret: bool = False,
) -> jax.Array:
    assert block % LANE == 0, "keys are tiled 128 to a row"
    blocks = cdiv(n, block)
    partial_mins = pl.pallas_call(
        functools.partial(_md5_kernel, block=block, total=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[],
            out_specs=pl.BlockSpec((None, 1, 1), lambda i, tgt: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((blocks, 1, 1), jnp.int32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(target, jnp.int32))
    return jnp.min(partial_mins)
