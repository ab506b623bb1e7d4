"""HotSpot 5-point stencil as a Pallas TPU kernel.

TPU adaptation of Rodinia's shared-memory-tiled CUDA stencil: instead of a
thread-block halo staged in shared memory, each grid step processes a
``block_rows``-row slab in VMEM and receives its two halo rows as separate
inputs (the Lightning chunk-halo made explicit — the same rows a
``StencilDist`` chunk replicates).  The halo rows are stored ``(n_blocks, 1,
cols)`` so each grid step's ``(1, cols)`` block spans the array's last two
dims whole, which is what the TPU tiling rule asks of a one-row block.  The
column halo is handled by shifting within the slab; row decomposition matches
the paper's column-wise HotSpot distribution with per-iteration halo
exchange.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import SUBLANE, cdiv
from .ref import DEFAULTS

#: Bytes of one ``temp`` slab per grid step when ``block_rows`` is not given:
#: in, power and out double-buffered plus the shifted temporaries stay well
#: inside the 16 MiB scoped-VMEM default.
SLAB_BYTES = 1 << 20


def default_block_rows(rows: int, cols: int, itemsize: int) -> int:
    """Rows per slab: ~``SLAB_BYTES``, a multiple of 8, at most ``rows``."""
    br = max(SUBLANE, SLAB_BYTES // (cols * itemsize) // SUBLANE * SUBLANE)
    return min(br, rows)


def _hotspot_kernel(t_ref, up_ref, down_ref, p_ref, o_ref, *,
                    sdc, rx, ry, rz, amb):
    centre = t_ref[...]  # (block_rows, cols)
    p = p_ref[...]
    up = jnp.concatenate([up_ref[...], centre[:-1, :]], axis=0)
    down = jnp.concatenate([centre[1:, :], down_ref[...]], axis=0)
    left = jnp.concatenate([centre[:, :1], centre[:, :-1]], axis=1)
    right = jnp.concatenate([centre[:, 1:], centre[:, -1:]], axis=1)
    delta = sdc * (
        (left + right - 2.0 * centre) * rx
        + (up + down - 2.0 * centre) * ry
        + (amb - centre) * rz
        + p
    )
    o_ref[...] = centre + delta


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "sdc", "rx", "ry",
                              "rz", "amb"),
)
def hotspot_pallas(
    temp: jax.Array,
    power: jax.Array,
    up: jax.Array | None = None,
    down: jax.Array | None = None,
    *,
    block_rows: int = 256,
    sdc: float = DEFAULTS["sdc"],
    rx: float = DEFAULTS["rx"],
    ry: float = DEFAULTS["ry"],
    rz: float = DEFAULTS["rz"],
    amb: float = DEFAULTS["amb"],
    interpret: bool = False,
) -> jax.Array:
    """``up``/``down`` are the ``(1, cols)`` rows just outside the slab (a
    neighbour's halo); ``None`` clamps to the slab's own edge row."""
    rows, cols = temp.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, "ops.py pads rows to a block multiple"
    n_blocks = cdiv(rows, block_rows)

    # Halo rows per block: the row above block i and the row below it, with
    # the slab's outer neighbours (or the clamped edge) at the two ends.
    starts = jnp.arange(n_blocks) * block_rows
    up_halo = temp[jnp.maximum(starts - 1, 0)]  # (n_blocks, cols)
    down_halo = temp[jnp.minimum(starts + block_rows, rows - 1)]
    if up is not None:
        up_halo = up_halo.at[0].set(up[0])
    if down is not None:
        down_halo = down_halo.at[-1].set(down[0])

    slab = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    halo = pl.BlockSpec((None, 1, cols), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _hotspot_kernel, sdc=sdc, rx=rx, ry=ry, rz=rz, amb=amb
        ),
        grid=(n_blocks,),
        in_specs=[slab, halo, halo, slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((rows, cols), temp.dtype),
        interpret=interpret,
    )(temp, up_halo[:, None, :], down_halo[:, None, :], power)
