"""``shard_map``-level collectives with explicit ``axis_name`` plumbing.

These are the distribution layer's compute/communication-overlap
primitives (paper §4.2: Lightning overlaps chunk transfers with kernel
execution; here the same idea applied to the collectives the sharding
rules imply):

* :func:`ring_allgather_matmul` — collective matmul for contraction-sharded
  operands (``x`` column-sharded, ``w`` row-sharded over ``axis_name``).
  Each device contributes a rank-``k/n`` partial product; the partials are
  combined with a bandwidth-optimal two-phase ring (reduce-scatter the
  output rows chunk-by-chunk, then ring all-gather the reduced chunks), so
  every ``ppermute`` hop can overlap with the local adds instead of
  serialising behind one monolithic all-reduce.
* :func:`hierarchical_grad_allreduce` — two-level gradient reduction:
  reduce over the fast intra-pod axes first, then once over the slow
  cross-pod (DCN) axes, so the expensive hop carries already-reduced data.

All functions are written against named axes only — they run under
``jax.shard_map`` on any mesh, including the fake
host-device meshes of the test harness.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs.trace import NULL_TRACER

# Module-level tracer hook: ``set_tracer(tracer)`` makes every collective
# emit a ``dist``-stream span through the same machinery the simulator and
# serve engine use, so dist traffic lands on the same Perfetto timeline.
# Spans are recorded when the collective is *traced/launched* by JAX (under
# ``jit`` that is trace time, not device execution time) — they mark which
# collectives a step issues and their payload sizes, not device-side
# duration.  The default NULL_TRACER keeps this zero-cost.
_TRACER = NULL_TRACER


def set_tracer(tracer) -> object:
    """Install a :class:`repro.obs.trace.Tracer` for collective spans;
    returns the previous tracer so callers can restore it."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer if tracer is not None else NULL_TRACER
    return prev


def _span(name: str, **args):
    return _TRACER.span(name, worker=0, stream="dist", cat="dist", **args)


def _axis_size(axis_name: str) -> int:
    # psum of a concrete constant folds to the (static) axis size.
    return int(lax.psum(1, axis_name))


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring all-reduce built from ``ppermute`` hops.

    Uses the bandwidth-optimal reduce-scatter + all-gather schedule when
    the leading dim divides the ring size, otherwise falls back to the
    rotate-and-accumulate ring (n-1 hops of the full tensor)."""
    n = _axis_size(axis_name)
    with _span("collective:ring_allreduce", axis=axis_name, n=n,
               size=int(math.prod(x.shape))):
        if n == 1:
            return x
        if x.ndim >= 1 and x.shape[0] % n == 0:
            return _ring_allreduce_two_phase(x, axis_name, n)
        return _ring_allreduce_rotate(x, axis_name, n)


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_allreduce_rotate(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    perm = _ring_perm(n)
    acc = x
    send = x
    for _ in range(n - 1):
        send = lax.ppermute(send, axis_name, perm)
        acc = acc + send
    return acc


def _ring_allreduce_two_phase(
    x: jax.Array, axis_name: str, n: int
) -> jax.Array:
    """Reduce-scatter ring then all-gather: 2(n-1) hops of 1/n the bytes."""
    perm = _ring_perm(n)
    idx = lax.axis_index(axis_name)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])

    def chunk(i):
        return lax.dynamic_index_in_dim(chunks, jnp.mod(i, n), 0,
                                        keepdims=False)

    # Phase 1 — reduce-scatter: at step s device i forwards the running sum
    # of chunk (i - s) and folds its local copy of chunk (i - s - 1) into
    # what arrives; after n-1 steps it owns fully-reduced chunk (i + 1) % n.
    send = chunk(idx)
    for s in range(n - 1):
        recv = lax.ppermute(send, axis_name, perm)
        send = recv + chunk(idx - s - 1)

    # Phase 2 — all-gather the reduced chunks.  Device j holds chunk
    # (j + 1) % n, so gathering by device index needs a roll of 1 to
    # restore chunk order.
    parts = lax.all_gather(send, axis_name)
    parts = jnp.roll(parts, 1, axis=0)
    return parts.reshape(x.shape)


def ring_allgather_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    axis_name: str,
    precision: Any = None,
) -> jax.Array:
    """Collective matmul for contraction-sharded operands.

    Inside ``shard_map`` with ``in_specs=(P(None, axis), P(axis, None))``:
    ``x`` holds a column shard ``x[:, kᵢ]`` and ``w`` the matching row
    shard ``w[kᵢ, :]``, so the local dot is a full-shape partial product
    and the ring combines the ``n`` partials into the replicated result
    ``x @ w`` on every device."""
    with _span("collective:ring_allgather_matmul", axis=axis_name,
               m=int(x.shape[0]), k=int(x.shape[-1]), n=int(w.shape[-1])):
        partial = jnp.matmul(x, w, precision=precision)
        return ring_allreduce(partial, axis_name)


def hierarchical_grad_allreduce(
    grads: Any,
    intra_axes: Sequence[str] = ("data",),
    inter_axes: Sequence[str] = ("pod",),
) -> Any:
    """Pod-then-data two-level gradient all-reduce over a pytree.

    Reduces over the fast ``intra_axes`` (ICI, within a pod) first and only
    then over ``inter_axes`` (DCN, across pods), so the slow hop moves one
    already-reduced copy per pod.  Numerically equal to a flat
    ``psum(v, intra + inter)``; either axis group may be empty."""
    intra = tuple(intra_axes or ())
    inter = tuple(inter_axes or ())

    def reduce_leaf(v):
        if intra:
            v = lax.psum(v, intra)
        if inter:
            v = lax.psum(v, inter)
        return v

    leaves = jax.tree.leaves(grads)
    with _span("collective:hierarchical_grad_allreduce",
               intra=",".join(intra), inter=",".join(inter),
               leaves=len(leaves)):
        return jax.tree.map(reduce_leaf, grads)
