"""Shared helpers for the Pallas TPU kernels.

Every kernel in this package is compiled by Mosaic for the TPU
(``pl.pallas_call`` with explicit ``BlockSpec`` VMEM tiling, MXU-aligned
block shapes); that is how they run.  Off the TPU, the ``ops.py`` wrapper of
each kernel falls back to ``interpret=True``, which executes the kernel body
with XLA on the CPU, so the CPU test suite can check every kernel against
its ``ref.py`` oracle.  ``tests/test_tpu_compile.py`` compiles each kernel
for a described TPU v5e to catch what interpret mode cannot (tiling rules,
VMEM limits, lowerings).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.cache
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode: False on real TPU, True elsewhere (CPU CI)."""
    return not on_tpu()


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    """Pad ``axis`` up to a multiple (TPU tiles want 8/128-aligned dims)."""
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


# TPU tiling constants (v5e): VREG lane width 128, sublane 8.
LANE = 128
SUBLANE = 8
