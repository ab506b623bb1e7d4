"""Compile every Pallas kernel for a described TPU v5e, with no chip attached.

Interpret mode (how the CPU suite runs the kernels) accepts block shapes,
lowerings and VMEM footprints that Mosaic refuses.  Each test here compiles
one kernel ahead of time for one v5e chip, ``interpret=False``, and checks
that the program holds a Mosaic kernel (``tpu_custom_call``).

The paper's kernels compile at the widths ``chip_smoke.py`` runs them on the
chip (its ``CASES``); the four LM kernels at the widths of the model configs
that call them (stablelm-3b serving, rwkv6-3b, recurrentgemma-2b).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and every xdist worker
imports this file.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    decode_attention,
    flash_attention,
    rg_lru,
    wkv6,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

# stablelm-3b: 32 heads of 80 (MHA); 4 serving slots of max_len 552.
# rwkv6-3b: 40 heads of 64.  recurrentgemma-2b: LRU width 2560.
LM_CASES = {
    "flash_attention": (
        (((1, 32, 2048, 80), bf16),) * 3,
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)),
    "decode_attention": (
        (((4, 32, 80), bf16), ((4, 32, 552, 80), bf16),
         ((4, 32, 552, 80), bf16), ((4,), i32)),
        lambda q, k, v, n: decode_attention(q, k, v, kv_len=n,
                                            interpret=False)),
    "rwkv6": (
        (((1, 40, 2048, 64), bf16),) * 4 + (((40, 64), bf16),),
        lambda r, k, v, w, u: wkv6(r, k, v, w, u, return_state=True,
                                   interpret=False)),
    "rg_lru": (
        (((1, 2048, 2560), bf16),) * 2 + (((1, 2560), f32),),
        lambda la, gx, h0: rg_lru(la, gx, h0, return_state=True,
                                  interpret=False)),
}

# The paper kernels compile at the matmul precision the smoke runs them at.
ALL = {**{n: (c.shapes, c.call, chip_smoke.PRECISION)
          for n, c in chip_smoke.CASES.items()},
       **{n: (*c, None) for n, c in LM_CASES.items()}}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with the persistent compile cache off
    (entries compiled here could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_thirteen_kernels_covered():
    assert len(ALL) == 13


@pytest.mark.parametrize("name", sorted(ALL))
def test_kernel_compiles_for_v5e(name, one_chip):
    shapes, call, precision = ALL[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
