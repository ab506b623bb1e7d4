"""K-means (Rodinia) Lloyd iterations as a Lightning user runs them.

The points live in HBM.  One iteration is one ``Context.launch`` of the
assignment and partial-sum kernel, then the centroid update.

This file holds everything the benchmark knows of the configuration: the
data made from the seed, the program's iteration, a plain float32
reference of its own, the control (that reference at the next lower matmul
precision), and the work one iteration needs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from spans import span

#: The Pallas kernel this configuration runs; its roofline metric is
#: ``kmeans_roofline``.
KERNEL = "kmeans"

ANNOTATION = ("global i => read points[i,:], read centroids[:,:], "
              "reduce(+) sums[:,:], reduce(+) counts[:]")

#: Rows per partial sum in the reference: short sums, then a sum of them,
#: so that its float32 rounding stays far below the program's.
PARTIAL_ROWS = 1024


def work(cfg: dict, traffic: dict, chips: int) -> dict:
    """FLOPs and HBM bytes of one Lloyd iteration on one chip.  Each point
    is read once; its distance to each centroid takes 3 flops a feature
    (difference, square, sum) and adding it to its cluster's sum 1 a
    feature.  The update of k centroids is left out: it is k*f divisions."""
    n, f, k = traffic["points"], cfg["features"], cfg["clusters"]
    one = {"flops": float(3 * n * k * f + n * f), "bytes": float(4 * n * f)}
    return {"kernel": one, "step": dict(one)}


# --------------------------------------------------------------------------
# Data: block b of ``block_rows`` points depends on (seed, b) alone, so the
# reference can make any block again on the device.
# --------------------------------------------------------------------------


def _keys(seed: int):
    return jax.random.split(jax.random.key(seed))  # points, centroids


def _fill(key, first, count: int, rows: int, f: int):
    """Points ``first * rows`` to ``(first + count) * rows``, feature-major
    (f, n), block by block, so that one block's random bits are all that is
    ever held besides the points."""
    def fill(i, out):
        x = jax.random.uniform(jax.random.fold_in(key, first + i), (f, rows))
        return jax.lax.dynamic_update_slice(out, x, (0, i * rows))

    out = jnp.zeros((f, count * rows), jnp.float32)
    return jax.lax.fori_loop(0, count, fill, out)


@functools.partial(jax.jit, static_argnames=("count", "rows", "f"))
def _blocks(key, first, count: int, rows: int, f: int):
    """The points as the program takes them, (n, f): XLA keeps so narrow an
    array feature-major, the layout the kernel reads, so the transpose
    makes no padded copy."""
    return _fill(key, first, count, rows, f).T


def initial_centroids(seed: int, cfg: dict) -> jax.Array:
    return jax.random.uniform(_keys(seed)[1],
                              (cfg["clusters"], cfg["features"]))


# --------------------------------------------------------------------------
# The program under test.
# --------------------------------------------------------------------------


def _body(views, _info):
    """The launch's kernel body: assignment and partial sums."""
    from repro.kernels import kmeans_assign_reduce

    sums, counts = kmeans_assign_reduce(views["points"], views["centroids"])
    return {"sums": sums, "counts": counts}


@jax.jit
def _update(sums, counts):
    return sums / jnp.maximum(counts, 1.0)[:, None]


class Program:
    """The cell's state and its iteration, built from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices,
                 tracer=None):
        from repro.core import Context, KernelDef

        assert len(devices) == 1, "kmeans cells run on one chip"
        n, rows = traffic["points"], traffic["block_rows"]
        assert n % rows == 0
        k, f = cfg["clusters"], cfg["features"]
        with jax.default_device(devices[0]):
            self.centroids = initial_centroids(seed, cfg)
            self.ctx = ctx = Context(tracer=tracer)
            pts = _blocks(_keys(seed)[0], 0, n // rows, rows, f)
            self.args = {"points": ctx.array(pts, name="points"),
                         "sums": ctx.zeros((k, f), name="sums"),
                         "counts": ctx.zeros((k,), name="counts")}
        self.grid = (n,)
        self.kernel = KernelDef.define(KERNEL, _body, ANNOTATION)

    def step(self) -> jax.Array:
        ctx = self.ctx
        with span("launch"):
            res = ctx.launch(self.kernel, grid=self.grid, args={
                **self.args,
                "centroids": ctx.array(self.centroids, name="centroids")})
        with span("update"):
            self.counts = res["counts"].value
            self.centroids = _update(res["sums"].value, self.counts)
        return self.centroids

    def check(self, steps: int) -> list[tuple[np.ndarray, np.ndarray]]:
        """Run the first ``steps`` iterations; after each, the counts of
        the launch's assignment and the updated centroids, on the host."""
        out = []
        for _ in range(steps):
            self.step()
            out.append((np.asarray(self.counts), np.asarray(self.centroids)))
        return out

    def free(self) -> None:
        self.args = self.ctx = self.kernel = None


# --------------------------------------------------------------------------
# Reference and comparison.
# --------------------------------------------------------------------------


#: Significand bits each operand keeps, per precision: float32 all 24
#: (``highest``); the 16 of two bfloat16 pieces that the TPU's three-pass
#: ``high`` matmul multiplies; bfloat16's 8.
BITS = {"highest": 24, "high": 16, "bfloat16": 8}


def _operand(x, precision: str):
    """``x`` as a matmul at ``precision`` sees it: rounded, to even, to its
    leading significand bits, 8 at a time.  The rounding clears mantissa
    bits rather than converting, a round trip XLA may skip."""
    def top_bits(v):  # v rounded to bfloat16's 8 leading bits, to even
        u = jax.lax.bitcast_convert_type(v, jnp.uint32)
        u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
        return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    if precision == "highest":
        return x
    out, rest = 0.0, x
    for _ in range(BITS[precision] // 8):
        head = top_bits(rest)
        out, rest = out + head, rest - head
    return out


@functools.partial(jax.jit, static_argnames=("rows", "f", "precision",
                                             "sums_precision"))
def _ref_block(key, b, centroids, rows: int, f: int, precision: str,
               sums_precision: str):
    """Sums and counts of block ``b``'s nearest-centroid assignment, as
    Rodinia computes it: the squared distance summed feature by feature
    (no matmul, no cancellation), the first nearest centroid, and the sums
    of each cluster's points in short partial sums."""
    xt = _fill(key, b, 1, rows, f)  # (f, rows)
    k = centroids.shape[0]
    xd, cd = _operand(xt, precision), _operand(centroids, precision)
    d2 = jnp.stack([jnp.sum((xd - cd[j][:, None]) ** 2, axis=0)
                    for j in range(k)])  # (k, rows)
    near = jnp.argmin(d2, axis=0)
    p = min(PARTIAL_ROWS, rows)
    xs = _operand(xt, sums_precision).reshape(f, -1, p)
    mine = [(near == j).reshape(1, -1, p) for j in range(k)]
    sums = jnp.stack([jnp.where(m, xs, 0.0).sum(axis=2).sum(axis=1)
                      for m in mine])
    return sums, jnp.stack([jnp.sum(m) for m in mine]).astype(jnp.float32)


def _step(cfg, traffic, seed, c, precision="highest", fault=None):
    """One Lloyd iteration of the reference from centroids ``c``: the counts
    of its assignment and the updated centroids.  ``fault`` plants one of
    ``FAULTS``, for the readings a limit is set from."""
    rows, f = traffic["block_rows"], cfg["features"]
    key = _keys(seed)[0]
    sums_precision = "bfloat16" if fault == "bf16_sums" else precision
    sums = counts = 0.0
    for b in range(0, traffic["points"] // rows, 2 if fault == "half" else 1):
        s, m = _ref_block(key, b, c, rows, f, precision, sums_precision)
        sums, counts = sums + s, counts + m
    if fault != "stale":
        c = sums / jnp.maximum(counts, 1.0)[:, None]
    return np.asarray(counts), np.asarray(c)


def _lloyd(cfg, traffic, seed, device, precision="highest",
           fault=None) -> list[tuple[np.ndarray, np.ndarray]]:
    """The reference's own Lloyd iterations from the seed, in the program's
    place: after each checked iteration, its counts and centroids."""
    out = []
    with jax.default_device(device):
        c = initial_centroids(seed, cfg)
        for _ in range(cfg["check_steps"]):
            out.append(_step(cfg, traffic, seed, c, precision, fault))
            c = out[-1][1]
    return out


def readings(cfg: dict, traffic: dict, seed: int, devices, got) -> dict:
    """The checked iterations against the float32 reference, each from the
    centroids that the program's previous iteration left (the first from
    the seed's), so that every iteration is judged on its own:

    * ``update_err``: the gap between the program's centroids and the
      reference's, relative to how far the reference's first iteration
      moved each (or the median cluster, where that moved further), the
      worst cluster, the mean over the iterations.  It reads the sums and
      counts the kernel returns; centroids left where they were read 1;
    * ``moved_share``: the share of the points that the program assigned to
      another cluster than the reference did, by the counts, the mean over
      the iterations.  Printed, not compared: flows that cancel in the
      counts let the control read as low as sound runs.
    """
    c = np.asarray(initial_centroids(seed, cfg), np.float64)
    errs, moved, scale = [], [], None
    with jax.default_device(devices[0]):
        for counts, cent in got:
            want_n, want = _step(cfg, traffic, seed, jnp.asarray(c, jnp.float32))
            want = np.asarray(want, np.float64)
            if scale is None:
                moves = np.linalg.norm(want - c, axis=1)
                scale = np.maximum(moves, np.median(moves))
            cent = np.asarray(cent, np.float64)
            errs.append(np.max(np.linalg.norm(cent - want, axis=1) / scale))
            moved.append(np.abs(counts - want_n).sum() / 2 / traffic["points"])
            c = cent
    return {"update_err": float(np.mean(errs)),
            "moved_share": float(np.mean(moved))}


def control(cfg: dict, traffic: dict, seed: int, devices) -> list:
    """The control: the reference at ``high``, the precision below the
    configuration's ``highest``, in the program's place: every operand
    rounded to the 16 bits that the TPU's three bfloat16 passes keep."""
    return _lloyd(cfg, traffic, seed, devices[0], "high")


#: Faults planted in the reference put in the program's place, read at the
#: cell's size by ``control.py --fault-seeds``: the centroids left where
#: they were, the sums taken over points rounded to bfloat16, half of the
#: points left out.
FAULTS = ("stale", "bf16_sums", "half")


def fault(name: str, cfg: dict, traffic: dict, seed: int, devices) -> list:
    return _lloyd(cfg, traffic, seed, devices[0], fault=name)
