"""Oracle for the N-Body benchmark (CUDA samples; paper §4.2).

All-pairs gravitational interaction with Plummer softening:

    a_i = Σ_j  m_j * (p_j − p_i) / (|p_j − p_i|² + ε²)^{3/2}

Positions are (n, 4): xyz + mass (the CUDA sample's float4 layout).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SOFTENING2 = 1e-3


def nbody_forces_ref(
    posm: jax.Array,
    softening2: float = SOFTENING2,
    targets: jax.Array | None = None,
) -> jax.Array:
    """Accelerations (n, 3) of every body, or of the ``(m, 4)`` bodies in
    ``targets`` (m, 3) under the pull of all of ``posm``."""
    pos = posm[:, :3]
    mass = posm[:, 3]
    tgt = pos if targets is None else targets[:, :3]
    d = pos[None, :, :] - tgt[:, None, :]  # (i, j, 3): p_j - p_i
    dist2 = jnp.sum(d * d, axis=-1) + softening2
    inv_d3 = jax.lax.rsqrt(dist2) / dist2  # 1 / dist^3
    return jnp.einsum("ij,ijk->ik", mass[None, :] * inv_d3, d)


def nbody_step_ref(
    posm: jax.Array,
    vel: jax.Array,
    dt: float = 0.01,
    softening2: float = SOFTENING2,
) -> tuple[jax.Array, jax.Array]:
    """Leapfrog-ish Euler step used by the sample (positions, velocities)."""
    acc = nbody_forces_ref(posm, softening2)
    vel = vel + dt * acc
    pos = posm[:, :3] + dt * vel
    return jnp.concatenate([pos, posm[:, 3:]], axis=1), vel
