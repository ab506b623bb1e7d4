"""RG-LRU linear recurrence as a Pallas TPU kernel.

The RecurrentGemma paper ships a custom Pallas kernel for exactly this scan
(their appendix notes the TPU scan is memory-bound); we implement the same
structure: channels are tiled across the grid's last axis (lane-aligned
blocks of 128), the (B, D-block) state vector lives in VMEM scratch, and
time streams through VMEM in ``block_t`` chunks.  Within a chunk the scan is
sequential — one VPU fma per step — which beats the O(log T) associative
scan on TPU because the recurrence is elementwise (no MXU work to amortize)
and the sequential form touches each input exactly once at full HBM
bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _rg_lru_kernel(la_ref, gx_ref, h0_ref, o_ref, hT_ref, h_ref,
                   *, block_t: int, t_steps: int):
    # Grid is (batch, d_block, t_block) — time innermost so the VMEM state
    # scratch is private to one (batch, channel-block) chain.
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[...]

    def step(i, _):
        a = jnp.exp(la_ref[0, i].astype(jnp.float32))  # (block_d,)
        beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0))
        x = beta * gx_ref[0, i].astype(jnp.float32)
        h = a * h_ref[0] + x
        h_ref[0] = h
        o_ref[0, i] = h.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, block_t, step, (), unroll=False)

    @pl.when(ti == t_steps - 1)
    def _flush():
        hT_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def rg_lru_pallas(
    log_a: jax.Array,  # (B, T, D)
    gx: jax.Array,  # (B, T, D)
    h0: jax.Array,  # (B, D) f32
    *,
    block_t: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, t, d = log_a.shape
    block_t = min(block_t, t)
    block_d = min(block_d, d)
    assert t % block_t == 0 and d % block_d == 0, "ops.py pads"
    t_steps = cdiv(t, block_t)
    grid = (b, cdiv(d, block_d), t_steps)
    # States are carried (B, 1, D) so a (1, block_d) block spans the
    # array's full second-minor dim, as TPU tiling requires.  The scan reads
    # and writes one time row at a time, which Mosaic addresses only in
    # 32-bit types (bf16 packs two rows to a sublane), so it runs in f32.
    state = pl.BlockSpec((None, 1, block_d), lambda b_, j, i: (b_, 0, j))

    out, h_final = pl.pallas_call(
        functools.partial(_rg_lru_kernel, block_t=block_t, t_steps=t_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda b_, j, i: (b_, i, j)),
            pl.BlockSpec((1, block_t, block_d), lambda b_, j, i: (b_, i, j)),
            state,
        ],
        out_specs=(
            pl.BlockSpec((1, block_t, block_d), lambda b_, j, i: (b_, i, j)),
            state,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(log_a.astype(jnp.float32), gx.astype(jnp.float32), h0[:, None, :])
    return out.astype(gx.dtype), h_final[:, 0, :]
