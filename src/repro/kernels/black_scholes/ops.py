"""Public wrapper for the Black-Scholes kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import LANE, interpret_default, round_up
from .kernel import black_scholes_pallas
from .ref import black_scholes_ref


def black_scholes(
    price: jax.Array,
    strike: jax.Array,
    years: jax.Array,
    *,
    block: int = 8 * 128 * 64,
    riskfree: float = 0.02,
    volatility: float = 0.30,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> tuple[jax.Array, jax.Array]:
    if use_ref:
        return black_scholes_ref(
            price, strike, years, riskfree=riskfree, volatility=volatility
        )
    interpret = interpret_default() if interpret is None else interpret
    (n,) = price.shape
    blk = min(block, round_up(max(1, n), LANE))
    target = round_up(n, blk)
    if target != n:
        pad = target - n
        one = jnp.ones((pad,), price.dtype)
        price = jnp.concatenate([price, one])
        strike = jnp.concatenate([strike, one])
        years = jnp.concatenate([years, one])
    call, put = black_scholes_pallas(
        price, strike, years, block=blk,
        riskfree=riskfree, volatility=volatility, interpret=interpret,
    )
    return call[:n], put[:n]
