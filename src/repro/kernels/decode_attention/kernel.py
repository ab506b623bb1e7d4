"""Flash-decode Pallas TPU kernel: one query token vs. a long KV cache.

The decode-step hotspot for the ``decode_32k`` / ``long_500k`` shapes: the
kernel is purely HBM-bandwidth-bound (the whole KV cache is read once per
token), so the tiling goal is streaming KV blocks through VMEM at full
bandwidth.  The sequence axis is the inner grid dimension with running
max / denominator in VMEM scratch (online softmax).  The kernel also emits
the per-(batch, head) log-sum-exp so sequence-sharded KV (one shard per
device along the ``model`` axis) can combine partial results with a psum —
the flash-decode trick, used by the planner's sequence-parallel KV
distribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv

NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, scale: float, block_k: int, kv_steps: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (group, d) — all query heads of one kv head group
    k = k_ref[0, 0]  # (block_k, d)
    v = v_ref[0, 0]  # (block_k, d)
    valid_len = len_ref[bi]  # scalar-prefetched into SMEM

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (hq, bk)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    s = jnp.where(k_pos < valid_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(ki == kv_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention_pallas(
    q: jax.Array,  # (B, HQ, D)
    k: jax.Array,  # (B, HKV, T, D)
    v: jax.Array,  # (B, HKV, T, D)
    kv_len: jax.Array,  # (B,) int32
    *,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, hq, d = q.shape
    _, hkv, t, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    block_k = min(block_k, t)
    assert t % block_k == 0, "ops.py pads the cache"
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_steps = cdiv(t, block_k)
    # Grid: one program per (batch, kv head); all `group` query heads of
    # that kv head processed together (rows of the MXU matmul).
    q_grouped = q.reshape(b, hkv, group, d)
    grid = (b, hkv, kv_steps)

    # kv_len is scalar-prefetched (SMEM), so index maps take it as a
    # trailing argument; the lse leaves as (group, 1) columns, which span
    # the array's last two dims as TPU tiling requires.
    out, lse = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block_k=block_k, kv_steps=kv_steps
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda b_, h, j, lens: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h, j, lens: (b_, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b_, h, j, lens: (b_, h, j, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, 1, group, d),
                             lambda b_, h, j, lens: (b_, h, 0, 0)),
                pl.BlockSpec((1, 1, group, 1),
                             lambda b_, h, j, lens: (b_, h, 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
        ),
        interpret=interpret,
    )(kv_len, q_grouped, k, v)
    return out.reshape(b, hq, d), lse.reshape(b, hq)
