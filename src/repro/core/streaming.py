"""Chunk streaming: process arrays larger than device memory (paper §3.4).

On GPU, Lightning spills chunks to host memory and overlaps the PCIe
transfers with kernel execution.  The TPU-idiomatic equivalent keeps the
big array in *host* memory (numpy) and streams fixed-size chunks through
the device with double buffering: while chunk *i* computes, chunk *i+1* is
already being transferred (`jax.device_put` is async), so transfer and
compute overlap exactly like the paper's memory-manager pipeline.

``stream_map_reduce`` folds a per-chunk kernel over host chunks with a
working set of exactly two chunks regardless of the total data size.
``stream_kmeans``, the paper's K-Means spilling experiment, is a launch
through :class:`~repro.core.launch.Context` with the points on the host
tier: the same streaming, planned in superblocks by Lightning's planner.
"""

from __future__ import annotations

from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kmeans import kmeans_assign_reduce, kmeans_assign_reduce_ref

from .launch import Context, KernelDef
from .superblock import BlockWork


def iter_chunks(array: np.ndarray, chunk_rows: int) -> Iterable[np.ndarray]:
    for start in range(0, array.shape[0], chunk_rows):
        yield array[start : start + chunk_rows]


def stream_map_reduce(
    data: np.ndarray,  # host-resident (the "spilled" tier)
    kernel: Callable[[jax.Array], jax.Array],  # per-chunk device kernel
    combine: Callable[[jax.Array, jax.Array], jax.Array],
    init: jax.Array,
    *,
    chunk_rows: int,
    pad_value=0,
) -> jax.Array:
    """Fold ``combine(acc, kernel(chunk))`` over host-resident chunks with
    double buffering.  Device working set: two chunks + the accumulator.

    The final (ragged) chunk is padded to ``chunk_rows`` so the jitted
    kernel compiles once; kernels must be padding-safe (the paper's kernels
    guard with bounds checks; ours use neutral pad values).

    To be removed once out-of-core writes (``black_scholes.spill``) go
    through ``Context.launch``, whose streamed launch does this fold for
    reduce outputs.
    """
    kernel = jax.jit(kernel)
    combine = jax.jit(combine)

    def put(chunk: np.ndarray) -> tuple[jax.Array, int]:
        n = chunk.shape[0]
        if n < chunk_rows:
            pad = np.full(
                (chunk_rows - n,) + chunk.shape[1:], pad_value, chunk.dtype
            )
            chunk = np.concatenate([chunk, pad])
        return jax.device_put(chunk), n  # async H2D

    acc = init
    it = iter_chunks(data, chunk_rows)
    try:
        nxt = put(next(it))
    except StopIteration:
        return acc
    while nxt is not None:
        cur, _n = nxt
        # Enqueue the next transfer BEFORE computing on the current chunk:
        # device_put is asynchronous, so the copy overlaps the kernel.
        try:
            nxt = put(next(it))
        except StopIteration:
            nxt = None
        acc = combine(acc, kernel(cur))
    return acc


_KMEANS_ANNOTATION = ("global i => read points[i,:], read centroids[:,:], "
                      "reduce(+) sums[:,:], reduce(+) counts[:]")


def _kmeans_body(assign):
    def body(views, _info):
        sums, counts = assign(views["points"], views["centroids"])
        return {"sums": sums, "counts": counts}
    return body


#: The assignment kernel by ``use_pallas``; kept, so that every call of
#: ``stream_kmeans`` runs the same per-chunk programs.
_KMEANS = {
    pallas: KernelDef.define("kmeans", _kmeans_body(assign),
                             _KMEANS_ANNOTATION)
    for pallas, assign in ((True, kmeans_assign_reduce),
                           (False, kmeans_assign_reduce_ref))}


def stream_kmeans(
    points: np.ndarray,  # (n, f) host-resident, any size
    centroids: jax.Array,  # (k, f) device-resident
    *,
    chunk_rows: int = 1 << 20,
    use_pallas: bool = True,
) -> jax.Array:
    """One K-Means iteration over host-resident data of any size — the
    paper's flagship spilling experiment (Figs. 10–12), end to end.  The
    points stay on the host tier of a :class:`Context` and one launch
    streams them in superblocks of ``chunk_rows`` points; the centroids are
    an argument of the launch, so repeated calls compile nothing."""
    # A budget of 0 keeps the points on the host tier whatever their size:
    # a narrow (n, f) array may not fit on the device even where its host
    # bytes do, and the caller asked for them to be streamed.
    ctx = Context(device_budget=0)
    centroids = jnp.asarray(centroids)
    k, f = centroids.shape
    res = ctx.launch(
        _KMEANS[use_pallas], grid=(points.shape[0],),
        work_dist=BlockWork(chunk_rows),
        args={"points": ctx.array(points, name="points"),
              "centroids": ctx.array(centroids, name="centroids"),
              "sums": ctx.zeros((k, f), name="sums"),
              "counts": ctx.zeros((k,), name="counts")})
    counts = jnp.maximum(res["counts"].value, 1.0)
    return (res["sums"].value / counts[:, None]).astype(centroids.dtype)
