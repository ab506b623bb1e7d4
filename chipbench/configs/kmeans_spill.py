"""K-means (Rodinia) Lloyd passes over points held in host memory, as a
Lightning user runs the paper's out-of-core k-means.

The points are larger than the chip's memory, so ``Context.array`` keeps
them on the host tier.  One iteration is one ``Context.launch`` of the same
assignment and partial-sum kernel as ``kmeans.py``, which streams the
points through the chip in the planner's superblocks, then the centroid
update.

The data, the reference, the control and the work are ``kmeans.py``'s,
loaded from beside this file: block b of the points depends on (seed, b)
alone, so the reference makes every block again on the device and never
reads the program's host copy.
"""

from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np


def _kmeans():
    name = "chipbench_configs_kmeans"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, Path(__file__).with_name("kmeans.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


km = _kmeans()

KERNEL = km.KERNEL
work = km.work
readings = km.readings
control = km.control
FAULTS = km.FAULTS
fault = km.fault


@functools.partial(jax.jit, static_argnames=("rows", "f"))
def _flat_block(key, b, rows: int, f: int):
    """Block ``b`` of ``kmeans.py``'s points, (rows, f) flattened row-major
    on the device, so that its copy to the host is a plain one."""
    return km._fill(key, b, 1, rows, f).T.reshape(-1)


def host_points(seed: int, n: int, rows: int, f: int) -> np.ndarray:
    """The points as ``kmeans.py`` makes them on the device, block by
    block, fetched into one (n, f) float32 host array; the next block is
    made and its copy to the host started while one is stored."""
    key = km._keys(seed)[0]
    out = np.empty((n, f), np.float32)

    def make(b):
        block = _flat_block(key, b, rows, f)
        block.copy_to_host_async()
        return block

    nxt = make(0)
    for b in range(n // rows):
        cur = nxt
        if (b + 1) * rows < n:
            nxt = make(b + 1)
        out[b * rows:(b + 1) * rows] = np.asarray(cur).reshape(rows, f)
    return out


class Program(km.Program):
    """``kmeans.py``'s iteration, with the points on the host tier."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices,
                 tracer=None):
        from repro.core import Context, KernelDef

        assert len(devices) == 1, "kmeans cells run on one chip"
        n, rows = traffic["points"], traffic["block_rows"]
        assert n % rows == 0
        k, f = cfg["clusters"], cfg["features"]
        with jax.default_device(devices[0]):
            self.centroids = km.initial_centroids(seed, cfg)
            self.ctx = ctx = Context(tracer=tracer)
            pts = ctx.array(host_points(seed, n, rows, f), name="points")
            assert pts.tier == "host", "the points fit on the device"
            self.args = {"points": pts,
                         "sums": ctx.zeros((k, f), name="sums"),
                         "counts": ctx.zeros((k,), name="counts")}
        self.grid = (n,)
        self.kernel = KernelDef.define(KERNEL, km._body, km.ANNOTATION)
