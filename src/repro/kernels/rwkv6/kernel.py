"""RWKV-6 WKV recurrence as a Pallas TPU kernel.

TPU adaptation of the official CUDA wkv6 kernel: CUDA parallelizes over
(batch × head × value-channel) threads with the K-dim state in registers;
on TPU the (K × V) state matrix lives in VMEM scratch and each time step is
a rank-1 update + matvec executed on the VPU (K×V elementwise) — time stays
sequential (the recurrence is inherently serial in its data-dependent decay)
while batch×head provides the grid parallelism.  Time is streamed in
``block_t`` chunks through VMEM so arbitrarily long sequences (the
``long_500k`` shape) never materialize more than one chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                 s_ref, *, block_t: int, t_steps: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    u = u_ref[0]  # (K,)

    def step(i, _):
        r_t = r_ref[0, 0, i]  # (K,)
        k_t = k_ref[0, 0, i]
        v_t = v_ref[0, 0, i]  # (V,)
        w_t = w_ref[0, 0, i]
        s = s_ref[...]  # (K, V)
        kv = k_t[:, None] * v_t[None, :]
        read = s + u[:, None] * kv
        o_ref[0, 0, i] = jnp.sum(
            r_t[:, None].astype(jnp.float32) * read, axis=0
        ).astype(o_ref.dtype)
        s_ref[...] = w_t[:, None] * s + kv
        return ()

    jax.lax.fori_loop(0, block_t, step, (), unroll=False)

    @pl.when(ti == t_steps - 1)
    def _flush():
        sT_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6_pallas(
    r: jax.Array,  # (B, H, T, K)
    k: jax.Array,
    v: jax.Array,  # (B, H, T, V)
    w: jax.Array,  # (B, H, T, K) decay in (0,1)
    u: jax.Array,  # (H, K)
    s0: jax.Array,  # (B, H, K, V) f32
    *,
    block_t: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    dtype = r.dtype
    block_t = min(block_t, t)
    assert t % block_t == 0, "ops.py pads time"
    t_steps = cdiv(t, block_t)
    grid = (b, h, t_steps)
    # The recurrence reads and writes one time row at a time, which Mosaic
    # addresses only in 32-bit types (bf16 packs two rows to a sublane), so
    # the kernel runs in f32.
    r, k, v, w, u = (x.astype(jnp.float32) for x in (r, k, v, w, u))

    out, s_final = pl.pallas_call(
        functools.partial(_wkv6_kernel, block_t=block_t, t_steps=t_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_t, dk), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_t, dk), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_t, dv), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_t, dk), lambda b_, h_, i: (b_, h_, i, 0)),
            # u is carried (H, 1, K): a (1, K) block spans the array's last
            # two dims, as TPU tiling requires of a one-row block.
            pl.BlockSpec((None, 1, dk), lambda b_, h_, i: (h_, 0, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_t, dv), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b_, h_, i: (b_, h_, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :], s0)
    return out.astype(dtype), s_final
