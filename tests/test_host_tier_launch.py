"""Out-of-core launches: an array larger than the device's budget stays in
host memory, and ``Context.launch`` streams the regions its superblocks read.

The CPU reports no memory limit, so each context here is given a small
device budget, which puts the points on the host tier at a size the CPU
runs in seconds.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BlockWork, Context, KernelDef
from repro.core.dist_array import DEVICE, HOST
from repro.kernels.kmeans import kmeans_assign_reduce
from repro.obs import MetricsRegistry, Tracer

N, F, K = 5000, 4, 5
ANNOTATION = ("global i => read points[i,:], read centroids[:,:], "
              "reduce(+) sums[:,:], reduce(+) counts[:]")
#: A budget well under the points' 80 kB: they stay on the host.
BUDGET = 1 << 15


def _body(use_ref):
    def body(views, _info):
        sums, counts = kmeans_assign_reduce(views["points"],
                                            views["centroids"],
                                            use_ref=use_ref)
        return {"sums": sums, "counts": counts}
    return body


#: Kept, so that every test's launches share their per-chunk programs.
KMEANS = {ref: KernelDef.define("kmeans", _body(ref), ANNOTATION)
          for ref in (False, True)}


def _data(seed=0):
    """Points around K far-apart centres, so that every assignment is far
    from a tie and the counts are exact in any precision."""
    rng = np.random.RandomState(seed)
    centres = rng.rand(K, F).astype(np.float32) * 20
    pts = centres[rng.randint(0, K, N)] + rng.randn(N, F).astype(
        np.float32) * 0.1
    return pts.astype(np.float32), centres


def _reference(pts, centroids):
    """Plain numpy, float64: nearest centroid, per-cluster sums and counts."""
    p = pts.astype(np.float64)
    c = np.asarray(centroids, np.float64)
    near = np.argmin(((p[:, None, :] - c[None]) ** 2).sum(-1), axis=1)
    sums = np.stack([p[near == j].sum(0) for j in range(K)])
    return sums, np.bincount(near, minlength=K).astype(np.float64)


def _launch(ctx, pts, centroids, use_ref=False, **kw):
    res = ctx.launch(KMEANS[use_ref], grid=(pts.shape[0],), args={
        "points": ctx.array(pts, name="points"),
        "centroids": ctx.array(jnp.asarray(centroids), name="centroids"),
        "sums": ctx.zeros((K, F), name="sums"),
        "counts": ctx.zeros((K,), name="counts")}, **kw)
    return np.asarray(res["sums"].value), np.asarray(res["counts"].value)


def test_array_tier_follows_the_budget():
    ctx = Context(device_budget=BUDGET)
    big = ctx.array(np.zeros((BUDGET // 4 + 1,), np.float32))
    small = ctx.array(np.zeros((BUDGET // 4,), np.float32))
    on_device = ctx.array(jnp.zeros((BUDGET,), jnp.float32))
    assert big.tier == HOST and isinstance(big.value, np.ndarray)
    assert small.tier == DEVICE and isinstance(small.value, jax.Array)
    assert on_device.tier == DEVICE
    assert big.meta() == ctx.array(jnp.zeros(big.shape), name=big.name).meta()
    # Without a budget, a device that reports no limit takes everything.
    assert Context().array(np.zeros((1 << 16,), np.float32)).tier == DEVICE


class _Device:
    """Stands in for a device that reports its memory."""

    def memory_stats(self):
        return {"bytes_limit": 1000, "bytes_in_use": 200}


def test_array_that_fits_in_free_device_memory_stays_on_the_device():
    ctx = Context()
    ctx._device = _Device()
    assert ctx.device_budget() == 800  # free bytes, no margin taken off
    assert ctx.array(np.zeros((200,), np.float32)).tier == DEVICE
    assert ctx.array(np.zeros((201,), np.float32)).tier == HOST


def test_superblocks_sized_so_two_take_an_eighth_of_the_budget():
    ctx = Context(device_budget=1 << 20)
    pts = ctx.array(np.zeros((1 << 17, F), np.float32))
    # 16 bytes a row; two superblocks in 128 kB: 4096 rows.
    assert pts.tier == HOST
    assert ctx._stream_rows(1 << 17, [pts]) == 4096


@pytest.mark.parametrize("use_ref", [False, True], ids=["pallas", "use_ref"])
def test_host_tier_kmeans_matches_the_reference(use_ref):
    pts, centres = _data()
    sums, counts = _launch(Context(device_budget=BUDGET), pts, centres,
                           use_ref)
    want_sums, want_counts = _reference(pts, centres)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(sums, want_sums, rtol=2e-6, atol=1e-3)


def test_device_tier_gives_the_same_answer():
    pts, centres = _data(1)
    ctx = Context(device_budget=BUDGET)
    host = _launch(ctx, pts, centres)
    device = _launch(Context(), pts, centres)
    np.testing.assert_array_equal(host[1], device[1])
    np.testing.assert_allclose(host[0], device[0], rtol=2e-6)


@pytest.mark.parametrize("rows,chunks", [(N, 1), (N // 2, 2), (1024, 5),
                                         (384, 14)],
                         ids=["one", "two", "many-ragged", "more-ragged"])
def test_chunk_count_does_not_change_the_answer(rows, chunks):
    pts, centres = _data(2)
    reg = MetricsRegistry()
    ctx = Context(device_budget=BUDGET, registry=reg)
    sums, counts = _launch(ctx, pts, centres, work_dist=BlockWork(rows))
    assert reg.snapshot()["launch.chunks{kernel=kmeans}"] == chunks
    want_sums, want_counts = _reference(pts, centres)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(sums, want_sums, rtol=2e-6, atol=1e-3)


def test_repeated_launch_compiles_and_traces_nothing():
    pts, centres = _data(3)
    reg = MetricsRegistry()
    tracer = Tracer(clock=time.perf_counter)
    ctx = Context(device_budget=BUDGET, registry=reg, tracer=tracer)
    work = BlockWork(1536)  # 4 chunks, the last one ragged: two shapes
    first = _launch(ctx, pts, centres, work_dist=work)
    before = reg.snapshot()
    again = _launch(ctx, pts, centres[::-1], work_dist=work)
    delta = MetricsRegistry.diff(reg.snapshot(), before)
    assert delta["launch.programs{kernel=kmeans}"] == 0
    last = [e for e in tracer.events if e["name"] == "launch:kmeans"][-1]
    assert last["args"]["programs"] == 0 and last["args"]["traces"] == 0
    # The same program, run with the new centroids.
    np.testing.assert_array_equal(again[1], first[1][::-1])


def test_at_most_two_chunks_are_on_the_device(monkeypatch):
    pts, centres = _data(4)
    put, live, placed = [], [], set()
    device_put = jax.device_put

    def counting_put(x, *a, **kw):
        out = device_put(x, *a, **kw)
        if isinstance(x, np.ndarray) and x.shape[1:] == (F,):  # a chunk
            put.append(out)
            live.append(sum(not p.is_deleted() for p in put))
            placed.add((out.shape, *out.devices()))
        return out

    monkeypatch.setattr(jax, "device_put", counting_put)
    ctx = Context(device_budget=BUDGET)
    for _ in range(2):  # the bound holds across launches too
        _launch(ctx, pts, centres, work_dist=BlockWork(512))
    assert len(put) == 20
    assert max(live) == 2
    # Each chunk goes to the context's device in the shape it is read in.
    assert placed == {((512, F), ctx.device), ((N - 9 * 512, F), ctx.device)}


def test_chunks_put_in_pieces_give_the_same_answer(monkeypatch):
    """A chunk large enough is put as ``_PUT_SPLIT`` pieces concurrently and
    joined on the device: the same answer, and still two chunks at most."""
    from repro.core import launch

    pts, centres = _data(5)
    whole = _launch(Context(device_budget=BUDGET), pts, centres,
                    work_dist=BlockWork(512))
    put, live = [], []
    device_put = jax.device_put

    def counting_put(x, *a, **kw):
        out = device_put(x, *a, **kw)
        if isinstance(x, np.ndarray) and x.shape[1:] == (F,):  # a piece
            put.append(out)
            live.append(sum(not p.is_deleted() for p in put))
        return out

    monkeypatch.setattr(jax, "device_put", counting_put)
    last = N - 9 * 512  # the ragged last chunk's rows
    monkeypatch.setattr(launch, "_PUT_MIN_BYTES", last // 4 * F * 4)
    pieces = _launch(Context(device_budget=BUDGET), pts, centres,
                     work_dist=BlockWork(512))
    np.testing.assert_array_equal(pieces[1], whole[1])
    np.testing.assert_allclose(pieces[0], whole[0], rtol=1e-6)
    assert len(put) == 10 * launch._PUT_SPLIT
    assert {p.shape for p in put} == {(128, F), (last // 4, F)}
    assert max(live) == 2 * launch._PUT_SPLIT


@pytest.mark.parametrize("annotation,what", [
    ("global i => write points[i,:]", "write access"),
    ("global i => readwrite points[i,:]", "readwrite access"),
    ("global i => read points[i-1:i+1,:], reduce(+) sums[:,:]",
     "overlapping regions"),
    ("global i => read points[:,:], reduce(+) sums[:,:]",
     "overlapping regions"),
    ("global i => read points[i,:], write sums[:,:]", "write of 'sums'"),
    ("global i => read points[i,:], reduce(+) out[i]", "reduce into a part"),
])
def test_write_or_halo_on_the_host_tier_raises(annotation, what):
    ctx = Context(device_budget=BUDGET)
    pts, _ = _data()
    k = KernelDef.define("bad", lambda v, i: {}, annotation)
    args = {"points": ctx.array(pts, name="points"),
            "sums": ctx.zeros((K, F), name="sums"),
            "out": ctx.zeros((N,), name="out")}
    args = {n: a for n, a in args.items() if n in k.annotation.arrays()}
    with pytest.raises(NotImplementedError, match=what):
        ctx.launch(k, grid=(N,), args=args, work_dist=BlockWork(1000))


def test_stream_span_and_counters():
    pts, centres = _data(5)
    reg = MetricsRegistry()
    tracer = Tracer(clock=time.perf_counter)
    ctx = Context(device_budget=BUDGET, registry=reg, tracer=tracer)
    _launch(ctx, pts, centres, work_dist=BlockWork(1024))
    (launch,) = [e for e in tracer.events if e["name"] == "launch:kmeans"]
    (execute,) = [e for e in tracer.events if e["name"] == "execute:kmeans"]
    (stream,) = [e for e in tracer.events if e["name"] == "stream:kmeans"]
    assert stream["parent"] == execute["id"]
    assert stream["args"]["launch"] == launch["args"]["launch"]
    assert stream["args"]["chunks"] == 5
    assert stream["args"]["bytes"] == pts.nbytes
    assert 0 <= stream["args"]["wait_s"] <= stream["dur"]
    snap = reg.snapshot()
    assert snap["launch.h2d_bytes{kernel=kmeans}"] == pts.nbytes
    assert snap["launch.chunks{kernel=kmeans}"] == 5
    # The counters count with the tracer off too, and no span is built.
    reg2 = MetricsRegistry()
    _launch(Context(device_budget=BUDGET, registry=reg2), pts, centres,
            work_dist=BlockWork(1024))
    assert reg2.snapshot()["launch.h2d_bytes{kernel=kmeans}"] == pts.nbytes
