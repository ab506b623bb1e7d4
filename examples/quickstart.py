"""Quickstart: the paper's Fig. 9 host-code example, in Lightning-JAX.

A 1-D stencil kernel with a data annotation, launched 10 times over a
distributed array with buffer swapping — the planner infers the halo
exchange and the cross-launch dependencies automatically.

Run:  PYTHONPATH=src python examples/quickstart.py
On a TPU host this runs on the chips: one chip, or a mesh over all of them.
On a CPU, XLA_FLAGS=--xla_force_host_platform_device_count=8 gives eight
virtual devices to try the mesh path.
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BlockWork,
    Context,
    KernelDef,
    StencilDist,
)


def main():
    # Mirror of paper Fig. 9: kernel definition with a data annotation.
    def stencil_body(views, info):
        x = views["input"]
        left = jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])
        right = jnp.concatenate([x[1:], jnp.zeros((1,), x.dtype)])
        return {"output": (left + x + right) / 3.0}

    stencil = KernelDef.define(
        "stencil",
        stencil_body,
        "global i => read input[i-1:i+1], write output[i]",
    )

    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        mesh = jax.make_mesh(
            (n_dev,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
        )
    ctx = Context(mesh=mesh)
    print(f"devices: {n_dev}")

    n = 1_000_000
    data_dist = StencilDist(n // max(1, n_dev), 1)  # chunk + halo of 1
    work_dist = BlockWork(n // max(1, n_dev))

    inp = ctx.ones((n,), dist=data_dist, name="input")
    out = ctx.zeros((n,), dist=data_dist, name="output")

    for i in range(10):
        res = ctx.launch(
            stencil, grid=(n,), work_dist=work_dist,
            args={"input": inp, "output": out},
        )
        inp, out = res["output"], inp  # swap, like the paper's host loop

    Context.synchronize(inp)
    rec = ctx.records[-1]
    print("result[0:4]      :", np.asarray(inp.value[:4]))
    print("comm per argument:", {k: v.value for k, v in rec.comm.items()})
    print("plan tasks       :", rec.plan.plan.counts())
    print("launches recorded:", len(ctx.records))


if __name__ == "__main__":
    main()
