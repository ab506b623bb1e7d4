"""Black-Scholes Pallas TPU kernel.

Pure VPU (vector unit) workload: one lane-dense ``(rows, 128)`` block per
grid step, no MXU.  The CUDA sample's per-thread scalar pipeline becomes an
(8, 128)-tiled elementwise program; arithmetic intensity is ~1 flop/byte so
the kernel is HBM-bound by construction (this is what makes it the paper's
worst spilling case, §4.3).

The cumulative normal distribution is the CUDA SDK sample's 5-term
polynomial (Abramowitz & Stegun 26.2.17, |error| < 7.5e-8): Mosaic has no
``erf`` lowering, and the polynomial is what the paper's benchmark runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import LANE, cdiv

_A = (0.31938153, -0.356563782, 1.781477937, -1.821255978, 1.330274429)
_RSQRT2PI = 0.39894228040143267793994605993438


def cnd_poly(d: jax.Array) -> jax.Array:
    """Cumulative normal distribution, CUDA SDK ``BlackScholes`` form."""
    k = 1.0 / (1.0 + 0.2316419 * jnp.abs(d))
    poly = k * (_A[0] + k * (_A[1] + k * (_A[2] + k * (_A[3] + k * _A[4]))))
    cnd = _RSQRT2PI * jnp.exp(-0.5 * d * d) * poly
    return jnp.where(d > 0, 1.0 - cnd, cnd)


def _bs_kernel(s_ref, k_ref, t_ref, call_ref, put_ref, *, riskfree, volatility):
    s = s_ref[...]
    k = k_ref[...]
    t = t_ref[...]
    sqrt_t = jnp.sqrt(t)
    d1 = (jnp.log(s / k) + (riskfree + 0.5 * volatility * volatility) * t) / (
        volatility * sqrt_t
    )
    d2 = d1 - volatility * sqrt_t
    cnd1 = cnd_poly(d1)
    cnd2 = cnd_poly(d2)
    exp_rt = jnp.exp(-riskfree * t)
    call_ref[...] = s * cnd1 - k * exp_rt * cnd2
    put_ref[...] = k * exp_rt * (1.0 - cnd2) - s * (1.0 - cnd1)


@functools.partial(
    jax.jit, static_argnames=("block", "interpret", "riskfree", "volatility")
)
def black_scholes_pallas(
    price: jax.Array,
    strike: jax.Array,
    years: jax.Array,
    *,
    block: int = 8 * 128 * 64,  # 64 VREG tiles per step
    riskfree: float = 0.02,
    volatility: float = 0.30,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    (n,) = price.shape
    block = min(block, n)
    assert n % block == 0 and block % LANE == 0, "ops.py pads to a block multiple"
    rows, block_rows = n // LANE, block // LANE
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    call, put = pl.pallas_call(
        functools.partial(_bs_kernel, riskfree=riskfree, volatility=volatility),
        grid=(cdiv(rows, block_rows),),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANE), price.dtype),
            jax.ShapeDtypeStruct((rows, LANE), price.dtype),
        ),
        interpret=interpret,
    )(*(x.reshape(rows, LANE) for x in (price, strike, years)))
    return call.reshape(n), put.reshape(n)
