"""Correlator Pallas TPU kernel.

TPU adaptation of the many-core correlator: the CUDA version tiles antenna
pairs into registers and streams samples; on TPU each channel's correlation
is four (ant × time)·(time × ant) matmuls on the MXU (re·re, im·im, im·re,
re·im), with time streamed in blocks through VMEM.  Channels form the outer
grid axis — the axis the paper distributes across GPUs — and time is the
accumulation axis with a VMEM scratch accumulator.

The re/im pair sits in front of (time, antenna) inside the kernel: as the
trailing axis it would pad 2 lanes to 128 in VMEM, a 64× blow-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _corr_kernel(s_ref, o_ref, vr_ref, vi_ref, *, t_steps: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        vr_ref[...] = jnp.zeros_like(vr_ref)
        vi_ref[...] = jnp.zeros_like(vi_ref)

    re = s_ref[0, 0]  # (block_t, ant)
    im = s_ref[0, 1]

    def tdot(x, y):  # xᵀ y, contracting time
        return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    vr_ref[...] += tdot(re, re) + tdot(im, im)
    vi_ref[...] += tdot(im, re) - tdot(re, im)

    @pl.when(t == t_steps - 1)
    def _flush():
        o_ref[0, 0] = vr_ref[...].astype(o_ref.dtype)
        o_ref[0, 1] = vi_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def correlate_pallas(
    samples: jax.Array,  # (channels, time, ant, 2)
    *,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    c, t, a, two = samples.shape
    assert two == 2
    block_t = min(block_t, t)
    assert t % block_t == 0, "ops.py pads time"
    t_steps = cdiv(t, block_t)
    grid = (c, t_steps)
    out = pl.pallas_call(
        functools.partial(_corr_kernel, t_steps=t_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2, block_t, a), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2, a, a), lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 2, a, a), samples.dtype),
        scratch_shapes=[
            pltpu.VMEM((a, a), jnp.float32),
            pltpu.VMEM((a, a), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.moveaxis(samples, 3, 1))
    return jnp.moveaxis(out, 1, 3)
