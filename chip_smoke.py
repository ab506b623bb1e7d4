#!/usr/bin/env python3
"""Smoke test of Lightning on TPU: the runtime's main path, on the chip.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases (one chip):

a. Device check: refuses to run unless JAX's first device is a TPU.
b. The paper's kernels (gemm, hotspot, kmeans, black_scholes, spmv_ell,
   md5, nbody, correlator, CGC cluster_sums) as ``KernelDef`` launches
   through ``Context.launch``, compiled by Mosaic (``interpret=False``), each
   checked for a ``tpu_custom_call`` in its HLO and against its ``ref.py``
   oracle.
c. Out of core: one ``stream_kmeans`` iteration over host data larger than
   the chip's memory, checked against the same iteration without Pallas.
d. One model at full width: ``run_serving("stablelm-3b")`` with random
   weights; every request must finish ``ok``.

With ``--chips 4`` only the mesh phase runs: HALO (hotspot), GATHER (gemm)
and REDUCE (kmeans) launches over a 4-chip ``Context``, each checked against
the same launch on one chip and against the oracle.

Each phase prints its wall and compile seconds.  Any failure exits non-zero
before the last line, which is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Everything runs in
this one process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    Context,
    KernelDef,
    ReplicatedDist,
    RowDist,
    StencilDist,
)
from repro.core.streaming import stream_kmeans  # noqa: E402
from repro.kernels import (  # noqa: E402
    black_scholes,
    black_scholes_ref,
    cluster_sums,
    cluster_sums_ref,
    correlate,
    correlate_ref,
    gemm,
    gemm_ref,
    hotspot_step,
    hotspot_step_ref,
    kmeans_assign_reduce,
    kmeans_assign_reduce_ref,
    md5_search,
    md5_search_ref,
    nbody_forces,
    nbody_forces_ref,
    spmv_ell,
    spmv_ell_ref,
)
from repro.kernels.md5.ref import md5_u32x2  # noqa: E402
from repro.launch.serve import run_serving  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

GiB = 1 << 30
f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def max_rel_err(got, want) -> float:
    """max |got - want| over max |want| (both as float32 on the host)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def expect_close(name: str, got, want, tol: float) -> float:
    err = max_rel_err(got, want)
    check(err <= tol, f"{name}: max rel err {err:.3g} > {tol:g}")
    return err


# ---------------------------------------------------------------------------
# Phase timing: wall seconds and the compile seconds JAX reports inside it.
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_: Any) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


@contextlib.contextmanager
def phase(name: str, report: dict):
    t0, c0 = time.perf_counter(), _compile_s[0]
    print(f"[{name}] start", flush=True)
    yield report
    wall, comp = time.perf_counter() - t0, _compile_s[0] - c0
    extra = " ".join(f"{k}={v}" for k, v in report.items())
    print(f"[{name}] wall_s={wall} compile_s={comp} {extra}", flush=True)


# ---------------------------------------------------------------------------
# Kernel calls at smoke width.  tests/test_tpu_compile.py compiles these same
# calls for a described v5e, so the shapes here are the ones that are
# guarded by the CPU test suite.
# ---------------------------------------------------------------------------

GEMM_N = 8192
HOTSPOT = (16384, 16384)
KMEANS = (1 << 26, 16, 32)  # points, features, clusters
BS_OPTIONS = 1 << 27
SPMV = (1 << 24, 16)  # rows (= columns of x), nnz slots per row
MD5_KEYS = (1 << 31) - (1 << 13)  # largest block multiple an int32 indexes
NBODY = 1 << 19
CORR = (64, 8192, 256)  # channels, time samples, antennas
CGC = (16384, 16384, 16, 16)  # rows, cols, row clusters, col clusters
# Four chips: each chip's share of a launch's inputs.  The one-chip launch
# it is compared with holds four shares, its written array's placeholder
# and its result, which must fit one chip's 16 GB.
PER_CHIP_BYTES = GiB
# Kernels and oracles run at full f32 matmul precision, so that they agree
# to rounding (at bf16 passes, near-tie points would change cluster).
PRECISION = "highest"


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One paper kernel: its argument shapes and its call."""

    shapes: tuple[tuple[tuple[int, ...], Any], ...]
    call: Callable[..., Any]


CASES: dict[str, KernelCase] = {
    "gemm": KernelCase(
        (((GEMM_N, GEMM_N), bf16),) * 2,
        lambda a, b: gemm(a, b, block_m=512, block_n=512, block_k=512,
                          interpret=False)),
    "hotspot": KernelCase(
        ((HOTSPOT, f32), (HOTSPOT, f32)),
        lambda t, p: hotspot_step(t, p, interpret=False)),
    "kmeans": KernelCase(
        (((KMEANS[0], KMEANS[1]), f32), ((KMEANS[2], KMEANS[1]), f32)),
        lambda p, c: kmeans_assign_reduce(p, c, interpret=False)),
    "black_scholes": KernelCase(
        (((BS_OPTIONS,), f32),) * 3,
        lambda s, k, t: black_scholes(s, k, t, interpret=False)),
    "spmv_ell": KernelCase(
        ((SPMV, f32), (SPMV, i32), ((SPMV[0],), f32)),
        lambda d, c, x: spmv_ell(d, c, x, interpret=False)),
    "md5": KernelCase(
        (((4,), jnp.uint32),),
        lambda tgt: md5_search(MD5_KEYS, tgt, interpret=False)),
    "nbody": KernelCase(
        (((NBODY, 4), f32),),
        lambda p: nbody_forces(p, interpret=False)),
    "correlator": KernelCase(
        (((CORR[0], CORR[1], CORR[2], 2), f32),),
        lambda s: correlate(s, interpret=False)),
    "cluster_sums": KernelCase(
        ((CGC[:2], f32), ((CGC[0],), i32), ((CGC[1],), i32)),
        lambda z, r, c: cluster_sums(z, r, c, CGC[2], CGC[3],
                                     interpret=False)),
}


def has_custom_call(call, *args) -> bool:
    return "tpu_custom_call" in jax.jit(call).lower(*args).as_text()


# ---------------------------------------------------------------------------
# Phase b: the paper's kernels through Context.launch on one chip.
# ---------------------------------------------------------------------------


def _launch_one(ctx: Context, name: str, annotation: str, body, grid,
                args: dict, rep: dict) -> dict:
    """Launch twice; the second, compiled launch's seconds go in ``rep``."""
    kd = KernelDef.define(name, body, annotation)
    for _ in range(2):
        t0 = time.perf_counter()
        out = ctx.launch(kd, grid=grid, args=args)
        Context.synchronize(*out.values())
        rep["launch_s"] = time.perf_counter() - t0
    return {k: v.value for k, v in out.items()}


def _run_kernel(ctx: Context, name: str, key) -> dict:
    """Generate data for one kernel from ``key``, launch it, check it."""
    case = CASES[name]
    call = case.call
    ks = jax.random.split(key, 4)
    rep: dict[str, Any] = {}

    if name == "gemm":
        a = jax.random.normal(ks[0], (GEMM_N, GEMM_N), f32).astype(bf16)
        b = jax.random.normal(ks[1], (GEMM_N, GEMM_N), f32).astype(bf16)
        check(has_custom_call(call, a, b), "gemm: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global [i, j] => read A[i,:], read B[:,j], "
            "write C[i,j]", lambda v, _: {"C": call(v["A"], v["B"])},
            (GEMM_N, GEMM_N), {"A": ctx.array(a), "B": ctx.array(b),
                               "C": ctx.zeros((GEMM_N, GEMM_N), bf16)}, rep)
        rep["err"] = expect_close(name, out["C"], gemm_ref(a, b), 2e-2)
    elif name == "hotspot":
        t = 60.0 + 30.0 * jax.random.uniform(ks[0], HOTSPOT, f32)
        p = jax.random.uniform(ks[1], HOTSPOT, f32) ** 2
        check(has_custom_call(call, t, p), "hotspot: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global [i, j] => read temp[i-1:i+1, j-1:j+1], "
            "read power[i,j], write out[i,j]",
            lambda v, _: {"out": call(v["temp"], v["power"])}, HOTSPOT,
            {"temp": ctx.array(t), "power": ctx.array(p),
             "out": ctx.zeros(HOTSPOT)}, rep)
        rep["err"] = expect_close(name, out["out"], hotspot_step_ref(t, p),
                                  1e-5)
    elif name == "kmeans":
        n, f, k = KMEANS
        pts = jax.random.uniform(ks[0], (n, f), f32)
        cen = jax.random.uniform(ks[1], (k, f), f32)
        check(has_custom_call(call, pts, cen), "kmeans: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global i => read points[i,:], read centroids[:,:], "
            "reduce(+) sums[:,:], reduce(+) counts[:]",
            lambda v, _: dict(zip(("sums", "counts"),
                                  call(v["points"], v["centroids"]))),
            (n,), {"points": ctx.array(pts), "centroids": ctx.array(cen),
                   "sums": ctx.zeros((k, f)), "counts": ctx.zeros((k,))}, rep)
        want_s, want_c = kmeans_ref_chunked(pts, cen)
        rep.update(kmeans_compare(name, out["sums"], out["counts"], want_s,
                                  want_c, n))
    elif name == "black_scholes":
        n = BS_OPTIONS
        s = 5.0 + 25.0 * jax.random.uniform(ks[0], (n,), f32)
        k = 1.0 + 99.0 * jax.random.uniform(ks[1], (n,), f32)
        y = 0.25 + 9.75 * jax.random.uniform(ks[2], (n,), f32)
        check(has_custom_call(call, s, k, y), "black_scholes: no custom call")
        out = _launch_one(
            ctx, name, "global i => read price[i], read strike[i], "
            "read years[i], write call[i], write put[i]",
            lambda v, _: dict(zip(("call", "put"), call(
                v["price"], v["strike"], v["years"]))),
            (n,), {"price": ctx.array(s), "strike": ctx.array(k),
                   "years": ctx.array(y), "call": ctx.zeros((n,)),
                   "put": ctx.zeros((n,))}, rep)
        want_call, want_put = black_scholes_ref(s, k, y)
        rep["err_call"] = expect_close("bs call", out["call"], want_call, 1e-5)
        rep["err_put"] = expect_close("bs put", out["put"], want_put, 1e-5)
    elif name == "spmv_ell":
        rows, nnz = SPMV
        mask = jax.random.uniform(ks[0], SPMV) < 0.7
        data = jnp.where(mask, jax.random.normal(ks[1], SPMV, f32), 0.0)
        cols = jax.random.randint(ks[2], SPMV, 0, rows, i32)
        x = jax.random.normal(ks[3], (rows,), f32)
        check(has_custom_call(call, data, cols, x), "spmv: no custom call")
        out = _launch_one(
            ctx, name, "global i => read data[i,:], read cols[i,:], "
            "read x[:], write y[i]",
            lambda v, _: {"y": call(v["data"], v["cols"], v["x"])}, (rows,),
            {"data": ctx.array(data), "cols": ctx.array(cols),
             "x": ctx.array(x), "y": ctx.zeros((rows,))}, rep)
        rep["err"] = expect_close(name, out["y"], spmv_ell_ref(data, cols, x),
                                  1e-5)
    elif name == "md5":
        # Plant one key: the search must find exactly it.
        planted = int(jax.random.randint(ks[0], (), MD5_KEYS // 2,
                                         MD5_KEYS - 1))
        w0 = jnp.asarray([planted], jnp.uint32)
        digest = md5_u32x2(w0, w0 ^ jnp.uint32(0x9E3779B9))
        target = tuple(int(d[0]) for d in digest)
        tgt = jnp.asarray(target, jnp.uint32)
        check(has_custom_call(call, tgt), "md5: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global i => read target[:], reduce(min) found[:]",
            lambda v, _: {"found": call(v["target"])[None]}, (MD5_KEYS,),
            {"target": ctx.array(tgt),
             "found": ctx.full((1,), MD5_KEYS, i32)}, rep)
        found = int(out["found"][0])
        window = min(1 << 20, planted + 1)
        ref = int(md5_search_ref(window, target, planted - window + 1))
        check(found == planted and ref == window - 1,
              f"md5: found {found}, planted {planted}, oracle {ref}")
        rep.update(planted=planted, found=found)
    elif name == "nbody":
        n = NBODY
        pos = jax.random.uniform(ks[0], (n, 3), f32)
        mass = 0.5 + jax.random.uniform(ks[1], (n, 1), f32)
        posm = jnp.concatenate([pos, mass], axis=1)
        check(has_custom_call(call, posm), "nbody: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global i => read posm[:,:], write acc[i,:]",
            lambda v, _: {"acc": call(v["posm"])}, (n,),
            {"posm": ctx.array(posm), "acc": ctx.zeros((n, 3))}, rep)
        rows = jax.random.choice(ks[2], n, (64,), replace=False)
        want = nbody_forces_ref(posm, targets=posm[rows])
        rep["err"] = expect_close(name, out["acc"][rows], want, 1e-3)
    elif name == "correlator":
        c, t, a = CORR
        s = jax.random.normal(ks[0], (c, t, a, 2), f32)
        check(has_custom_call(call, s), "correlator: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global [c, i, j] => read samples[c,:,:,:], "
            "write vis[c,i,j,:]", lambda v, _: {"vis": call(v["samples"])},
            (c, a, a), {"samples": ctx.array(s),
                        "vis": ctx.zeros((c, a, a, 2))}, rep)
        rep["err"] = expect_close(name, out["vis"], correlate_ref(s), 1e-5)
    elif name == "cluster_sums":
        n, m, r, cc = CGC
        z = jax.random.uniform(ks[0], (n, m), f32)
        ra = jax.random.randint(ks[1], (n,), 0, r, i32)
        ca = jax.random.randint(ks[2], (m,), 0, cc, i32)
        check(has_custom_call(call, z, ra, ca), "cgc: no tpu_custom_call")
        out = _launch_one(
            ctx, name, "global [i, j] => read z[i,j], read ra[i], "
            "read ca[j], reduce(+) sums[:,:]",
            lambda v, _: {"sums": call(v["z"], v["ra"], v["ca"])}, (n, m),
            {"z": ctx.array(z), "ra": ctx.array(ra), "ca": ctx.array(ca),
             "sums": ctx.zeros((r, cc))}, rep)
        rep["err"] = expect_close(name, out["sums"],
                                  cluster_sums_ref(z, ra, ca, r, cc), 1e-5)
    else:
        raise KeyError(name)
    return rep


def kmeans_ref_chunked(points, centroids, rows: int = 1 << 22):
    """The kmeans oracle summed over row chunks (its (n, k) distance
    matrix would not fit whole)."""
    ref = jax.jit(kmeans_assign_reduce_ref)
    sums = counts = 0.0
    for lo in range(0, points.shape[0], rows):
        s, c = ref(points[lo:lo + rows], centroids)
        sums, counts = sums + s, counts + c
    return sums, counts


def kmeans_compare(name, sums, counts, want_sums, want_counts, n) -> dict:
    """Counts may differ only by points that sit on a distance tie;
    sums then differ by those points' coordinates (each < 1)."""
    moved = float(np.abs(np.asarray(counts) - np.asarray(want_counts)).sum())
    check(float(np.asarray(counts).sum()) == n, f"{name}: counts sum != {n}")
    check(moved <= 1e-5 * n, f"{name}: {moved} points changed cluster")
    err = float(np.abs(np.asarray(sums) - np.asarray(want_sums)).max())
    scale = float(np.abs(np.asarray(want_sums)).max())
    check(err <= 1e-4 * scale + moved,
          f"{name}: sums err {err} vs scale {scale}")
    return {"moved": moved, "sums_err": err / scale}


def phase_kernels(seed: int) -> None:
    ctx = Context()
    key = jax.random.key(seed)
    for i, name in enumerate(CASES):
        with phase(f"b.{name}", {}) as rep:
            rep.update(_run_kernel(ctx, name, jax.random.fold_in(key, i)))


# ---------------------------------------------------------------------------
# Phase c: stream_kmeans over host data larger than the chip's memory.
# ---------------------------------------------------------------------------


def host_uniform(shape: tuple[int, int], seed: int, chunk_rows: int,
                 threads: int = 8) -> np.ndarray:
    """Uniform [0, 1) float32 host array, filled chunk by chunk in place
    from per-chunk child seeds, so host memory peaks at the array itself."""
    out = np.empty(shape, np.float32)
    starts = range(0, shape[0], chunk_rows)
    seeds = np.random.SeedSequence(seed).spawn(len(starts))

    def fill(i_lo):
        i, lo = i_lo
        np.random.default_rng(seeds[i]).random(
            out=out[lo:lo + chunk_rows], dtype=np.float32)

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, enumerate(starts)))
    return out


def phase_stream(seed: int, bytes_limit: int) -> None:
    f, k = KMEANS[1], KMEANS[2]
    chunk_rows = 1 << 22
    row_bytes = f * 4
    n = -(-int(1.25 * bytes_limit) // (row_bytes * chunk_rows)) * chunk_rows
    with phase("c.stream_kmeans", {}) as rep:
        t0 = time.perf_counter()
        points = host_uniform((n, f), seed, chunk_rows)
        rep["gen_s"] = time.perf_counter() - t0
        rep["bytes_streamed"] = points.nbytes
        rep["hbm_bytes_limit"] = bytes_limit
        rep["host_ram_avail"] = (os.sysconf("SC_AVPHYS_PAGES")
                                 * os.sysconf("SC_PAGE_SIZE"))
        rep["host_ram_total"] = (os.sysconf("SC_PHYS_PAGES")
                                 * os.sysconf("SC_PAGE_SIZE"))
        check(points.nbytes > bytes_limit, "dataset fits in HBM")
        cen = jax.random.uniform(jax.random.key(seed), (k, f), f32)
        t0 = time.perf_counter()
        got = np.asarray(stream_kmeans(points, cen, chunk_rows=chunk_rows))
        rep["pallas_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = np.asarray(stream_kmeans(points, cen, chunk_rows=chunk_rows,
                                        use_pallas=False))
        rep["reference_s"] = time.perf_counter() - t0
        rep["err"] = expect_close("stream_kmeans", got, want, 1e-4)
        del points


# ---------------------------------------------------------------------------
# Phase d: one model at full width through the serving entry point.
# ---------------------------------------------------------------------------


def phase_serve(seed: int) -> None:
    requests, prompt_len, max_new = 8, 512, 32
    with phase("d.serve_stablelm-3b", {}) as rep:
        res = run_serving("stablelm-3b", smoke=False, requests=requests,
                          prompt_len=prompt_len, max_new=max_new, slots=4,
                          seed=seed)
        rep.update({k: res[k] for k in ("completed", "statuses", "errors",
                                         "prefill_tokens", "decode_tokens",
                                         "wall_s")})
        check(res["errors"] == 0 and res["statuses"] == {"ok": requests},
              f"serving: {res['statuses']} {res['error_messages']}")
        check(res["prefill_tokens"] == requests * prompt_len
              and res["decode_tokens"] == requests * (max_new - 1),
              f"serving: token counts {res}")


# ---------------------------------------------------------------------------
# Four chips: HALO, GATHER and REDUCE launches over a mesh.
# ---------------------------------------------------------------------------


def _bytes_in_use() -> list[int]:
    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def _mesh_vs_one(ctx4: Context, kd: KernelDef, grid, host: dict,
                 dists: dict, outs: dict, pattern: tuple[str, str],
                 rep: dict) -> tuple[dict, dict]:
    """Launch ``kd`` on the mesh and on one chip; return both outputs on
    the host.  The mesh arrays are freed before the one-chip launch."""
    def args_for(ctx, mesh: bool):
        args = {n: ctx.array(v, dist=dists[n] if mesh else None, name=n)
                for n, v in host.items()}
        for n, (shape, dtype, fill) in outs.items():
            args[n] = ctx.full(shape, fill, dtype,
                               dist=dists[n] if mesh else None, name=n)
        return args

    args = args_for(ctx4, True)
    rep["bytes_in_use"] = _bytes_in_use()
    res = ctx4.launch(kd, grid=grid, args=args)
    mesh_out = {n: np.asarray(v.value) for n, v in res.items()}
    arg, want = pattern
    got = ctx4.records[-1].comm[arg].value
    check(got == want, f"{kd.name}: {arg} moved as {got}, not {want}")
    del args, res

    ctx1 = Context()
    args = args_for(ctx1, False)
    res = ctx1.launch(kd, grid=grid, args=args)
    one_out = {n: np.asarray(v.value) for n, v in res.items()}
    del args, res
    return mesh_out, one_out


def phase_four_chips(seed: int) -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}")
    mesh = jax.make_mesh((4,), ("data",), devices=devs[:4])
    ctx4 = Context(mesh=mesh)
    rng_seed = np.random.SeedSequence(seed).generate_state(3)
    per_chip = PER_CHIP_BYTES

    def spread_ok(rep):
        used = rep["bytes_in_use"][:4]
        check(min(used) >= per_chip and max(used) <= 2 * min(used),
              f"shards not spread: bytes_in_use {used}")

    # HALO: hotspot rows split over the chips; one halo row each way.
    rows, cols = 4 * per_chip // (16384 * 4 * 2), 16384  # temp and power
    with phase("4chip.halo_hotspot", {}) as rep:
        host = {"temp": 60.0 + 30.0 * host_uniform((rows, cols),
                                                   int(rng_seed[0]), 2048),
                "power": host_uniform((rows, cols), int(rng_seed[0]) + 1,
                                      2048) ** 2}

        def body(v, info):
            t, p = v["temp"], v["power"]
            up = down = None
            if t.shape[0] != p.shape[0]:  # mesh: halo rows, zero at edges
                first, last = info.device_index == 0, info.device_index == 3
                up = jnp.where(first, t[1:2], t[:1])
                down = jnp.where(last, t[-2:-1], t[-1:])
                t = t[1:-1]
            return {"out": hotspot_step(t, p, up=up, down=down,
                                        interpret=False)}

        kd = KernelDef.define(
            "hotspot", body, "global [i, j] => read temp[i-1:i+1, j-1:j+1], "
            "read power[i,j], write out[i,j]")
        dists = {"temp": StencilDist(rows // 4, 1), "power": RowDist(),
                 "out": RowDist()}
        mesh_out, one_out = _mesh_vs_one(
            ctx4, kd, (rows, cols), host, dists,
            {"out": ((rows, cols), f32, 0.0)}, ("temp", "halo"), rep)
        spread_ok(rep)
        rep["err_vs_one_chip"] = expect_close("halo", mesh_out["out"],
                                              one_out["out"], 1e-6)
        errs = []
        for b in [0, rows // 4, rows // 2, 3 * rows // 4, rows]:
            lo, hi = max(b - 32, 0), min(b + 32, rows)
            rlo, rhi = max(lo - 1, 0), min(hi + 1, rows)
            ref = hotspot_step_ref(jnp.asarray(host["temp"][rlo:rhi]),
                                   jnp.asarray(host["power"][rlo:rhi]))
            errs.append(expect_close("halo oracle", mesh_out["out"][lo:hi],
                                     ref[lo - rlo:hi - rlo], 1e-5))
        rep["err_oracle"] = max(errs)
        del host, mesh_out, one_out

    # GATHER: C = A @ B with A and C split by rows and B gathered whole.
    m, kk = 4 * per_chip // (16384 * 2), 16384
    with phase("4chip.gather_gemm", {}) as rep:
        host = {"A": host_uniform((m, kk), int(rng_seed[1]), 4096)
                .astype(jnp.bfloat16),
                "B": host_uniform((kk, kk), int(rng_seed[1]) + 1, 4096)
                .astype(jnp.bfloat16)}
        kd = KernelDef.define(
            "gemm", lambda v, _: {"C": gemm(v["A"], v["B"], block_m=512,
                                            block_n=512, block_k=512,
                                            interpret=False)},
            "global [i, j] => read A[i,:], read B[:,j], write C[i,j]")
        dists = {"A": RowDist(), "B": RowDist(), "C": RowDist()}
        mesh_out, one_out = _mesh_vs_one(
            ctx4, kd, (m, kk), host, dists, {"C": ((m, kk), bf16, 0.0)},
            ("B", "gather"), rep)
        spread_ok(rep)
        rep["err_vs_one_chip"] = expect_close("gather", mesh_out["C"],
                                              one_out["C"], 1e-2)
        rows_s = np.random.default_rng(seed).choice(m, min(m, 256), replace=False)
        rows_s = np.concatenate([rows_s, [0, m // 4 - 1, m // 4, m - 1]])
        want = gemm_ref(jnp.asarray(host["A"][rows_s]), jnp.asarray(host["B"]))
        rep["err_oracle"] = expect_close("gather oracle",
                                         mesh_out["C"][rows_s], want, 2e-2)
        del host, mesh_out, one_out

    # REDUCE: kmeans partial sums per chip, combined with psum.
    n, f, k = 4 * per_chip // (16 * 4), KMEANS[1], KMEANS[2]
    with phase("4chip.reduce_kmeans", {}) as rep:
        host = {"points": host_uniform((n, f), int(rng_seed[2]), 1 << 20),
                "centroids": host_uniform((k, f), int(rng_seed[2]) + 1, k)}
        kd = KernelDef.define(
            "kmeans", lambda v, _: dict(zip(("sums", "counts"),
                                            kmeans_assign_reduce(
                                                v["points"], v["centroids"],
                                                interpret=False))),
            "global i => read points[i,:], read centroids[:,:], "
            "reduce(+) sums[:,:], reduce(+) counts[:]")
        dists = {"points": RowDist(), "centroids": ReplicatedDist(),
                 "sums": ReplicatedDist(), "counts": ReplicatedDist()}
        mesh_out, one_out = _mesh_vs_one(
            ctx4, kd, (n,), host, dists,
            {"sums": ((k, f), f32, 0.0), "counts": ((k,), f32, 0.0)},
            ("sums", "reduce"), rep)
        spread_ok(rep)
        check(np.array_equal(mesh_out["counts"], one_out["counts"]),
              "reduce: counts differ from one chip")
        rep["err_vs_one_chip"] = expect_close("reduce", mesh_out["sums"],
                                              one_out["sums"], 1e-5)
        want_s, want_c = kmeans_ref_chunked(jnp.asarray(host["points"]),
                                            jnp.asarray(host["centroids"]))
        rep.update(kmeans_compare("reduce oracle", mesh_out["sums"],
                                  mesh_out["counts"], want_s, want_c, n))
        del host, mesh_out, one_out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    with phase("a.device", {}) as rep:
        dev = jax.devices()[0]
        rep.update(platform=dev.platform, kind=repr(dev.device_kind),
                   count=len(jax.devices()))
        if dev.platform != "tpu":
            print(f"no TPU: JAX's first device is {dev.platform}",
                  file=sys.stderr)
            return 2
        rep["bytes_limit"] = dev.memory_stats()["bytes_limit"]
        rep["compile_cache"] = enable_compile_cache()
    bytes_limit = rep["bytes_limit"]

    with jax.default_matmul_precision(PRECISION):
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            phase_kernels(args.seed)
            phase_stream(args.seed, bytes_limit)
    if args.chips == 1:
        phase_serve(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
