"""Public wrapper for the HotSpot stencil kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import interpret_default, round_up
from .kernel import default_block_rows, hotspot_pallas
from .ref import DEFAULTS, hotspot_step_ref


def hotspot_step(
    temp: jax.Array,
    power: jax.Array,
    *,
    up: jax.Array | None = None,
    down: jax.Array | None = None,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
    **consts,
) -> jax.Array:
    """One HotSpot step.  Rows are padded to a block multiple with
    edge-replication so the clamp boundary condition is preserved.

    ``up``/``down`` are the ``(1, cols)`` neighbour rows outside a slab of a
    larger grid (a halo exchange); without them the slab's edges clamp.
    ``block_rows`` defaults to ~1 MiB slabs."""
    if use_ref:
        return hotspot_step_ref(temp, power, **{**DEFAULTS, **consts})
    interpret = interpret_default() if interpret is None else interpret
    rows, cols = temp.shape
    if block_rows is None:
        block_rows = default_block_rows(rows, cols, temp.dtype.itemsize)
    br = min(block_rows, rows)
    target = round_up(rows, br)
    if target != rows:
        pad = target - rows
        edge = temp[-1:, :] if down is None else down
        temp_p = jnp.concatenate([temp, jnp.tile(edge, (pad, 1))], 0)
        power_p = jnp.concatenate([power, jnp.zeros((pad, cols), power.dtype)], 0)
    else:
        temp_p, power_p = temp, power
    out = hotspot_pallas(
        temp_p, power_p, up, down, block_rows=br, interpret=interpret,
        **{**DEFAULTS, **consts},
    )
    return out[:rows, :]
