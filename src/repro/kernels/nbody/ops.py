"""Public wrappers for the N-Body kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import interpret_default, round_up
from .kernel import nbody_pallas
from .ref import SOFTENING2, nbody_forces_ref, nbody_step_ref


def nbody_forces(
    posm: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 128,
    softening2: float = SOFTENING2,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    if use_ref:
        return nbody_forces_ref(posm, softening2)
    interpret = interpret_default() if interpret is None else interpret
    n = posm.shape[0]
    bi, bj = min(block_i, n), min(block_j, n)
    target = round_up(round_up(n, bi), bj)
    if target != n:
        # Padding bodies have zero mass → contribute zero force.
        pad = jnp.zeros((target - n, 4), posm.dtype)
        posm_p = jnp.concatenate([posm, pad])
    else:
        posm_p = posm
    acc = nbody_pallas(
        posm_p, block_i=bi, block_j=bj, softening2=softening2,
        interpret=interpret,
    )
    return acc[:n]


def nbody_step(
    posm: jax.Array,
    vel: jax.Array,
    dt: float = 0.01,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    if kw.pop("use_ref", False):
        return nbody_step_ref(posm, vel, dt)
    acc = nbody_forces(posm, **kw)
    vel = vel + dt * acc
    pos = posm[:, :3] + dt * vel
    return jnp.concatenate([pos, posm[:, 3:]], axis=1), vel
