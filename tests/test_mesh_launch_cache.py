"""The cache of jitted mesh programs in ``Context._execute_mesh``.

One child process with 4 virtual CPU devices runs every launch below and
prints what each test checks: a cached launch (a hit) gives what the launch
that built its program gave, and what one built without caching gives, for
HALO, GATHER and REDUCE kernels; a change of grid, shape, dtype,
distribution or scalar value builds a new program and gives the right
answer; the least recently used program is dropped past the bound; and the
cache keeps no launch's input arrays alive.
"""

import json

import pytest

from _subproc import run_with_devices

SNIPPET = """
import gc, json, weakref
import jax, jax.numpy as jnp, numpy as np
import repro.core.launch as launch_mod
from repro.core import *
from repro.obs import MetricsRegistry

mesh = jax.make_mesh((4,), ("data",))
rng = np.random.RandomState(0)
got = {}


def count(reg, name, result):
    return reg.snapshot().get(
        f"launch.mesh_cache{{kernel={name},result={result}}}", 0)


def scaled(body):
    # Every kernel takes a scalar ``c``: a float is keyed and cached, a 0-d
    # numpy array cannot be hashed, so its launch builds a program uncached.
    return lambda v, i: {k: o * i.scalars["c"] for k, o in body(v, i).items()}


# -- a hit gives what the miss and an uncached build give ----------------------
n, m = 256, 64
x_np = rng.rand(n).astype(np.float32)
A_np = rng.rand(m, m).astype(np.float32)
B_np = rng.rand(m, m).astype(np.float32)
R_np = rng.rand(128, 16).astype(np.float32)
cases = {
    "halo": ("global i => read input[i-1:i+1], write output[i]",
             lambda v, i: {"output": (v["input"][:-2] + v["input"][1:-1]
                                      + v["input"][2:]) / 3.0},
             lambda ctx: {"input": ctx.array(x_np, dist=StencilDist(n // 4, 1)),
                          "output": ctx.zeros((n,), dist=BlockDist(n // 4))},
             (n,), "output",
             (np.pad(x_np, 1)[:-2] + x_np + np.pad(x_np, 1)[2:]) / 3.0),
    "gather": ("global [i, j] => read A[i,:], read B[:,j], write C[i,j]",
               lambda v, i: {"C": v["A"] @ v["B"]},
               lambda ctx: {"A": ctx.array(A_np, dist=RowDist()),
                            "B": ctx.array(B_np, dist=RowDist()),
                            "C": ctx.zeros((m, m), dist=RowDist())},
               (m, m), "C", A_np @ B_np),
    "reduce": ("global [i, j] => read A[i,j], reduce(+) s[j]",
               lambda v, i: {"s": v["A"].sum(axis=0)},
               lambda ctx: {"A": ctx.array(R_np, dist=RowDist()),
                            "s": ctx.zeros((16,), dist=ReplicatedDist())},
               (128, 16), "s", R_np.sum(axis=0)),
}
for case, (ann, body, make, grid, out, want) in cases.items():
    reg = MetricsRegistry()
    ctx = Context(mesh=mesh, registry=reg)
    k = KernelDef.define(case, scaled(body), ann, scalars=("c",))
    args = make(ctx)
    vals = [np.asarray(ctx.launch(k, grid=grid, args=args,
                                  scalars={"c": c})[out].value)
            for c in (1.0, 1.0, np.ones((), np.float32))]
    got[case] = {
        "pattern": sorted(p.value for p in ctx.records[-1].comm.values()),
        "miss": count(reg, case, "miss"), "hit": count(reg, case, "hit"),
        "hit_vs_miss": float(np.abs(vals[1] - vals[0]).max()),
        "hit_vs_uncached": float(np.abs(vals[1] - vals[2]).max()),
        "hit_vs_numpy": float(np.abs(vals[1] - want).max()),
        "scale": float(np.abs(want).max()),
    }

# -- a change of signature is a miss, and right ----------------------------------
reg = MetricsRegistry()
ctx = Context(mesh=mesh, registry=reg)
k = KernelDef.define(
    "affine", lambda v, i: {"y": v["x"] * i.scalars["c"] + i.grid[1]},
    "global [i, j] => read x[i, j], write y[i, j]", scalars=("c",))
kg = KernelDef.define("gemm", lambda v, i: {"C": v["A"] @ v["B"]},
                      "global [i, j] => read A[i,:], read B[:,j], write C[i,j]")
X = rng.randint(0, 50, (128, 16)).astype(np.float32)


def affine(rows=64, cols=16, grid_cols=16, dtype=np.float32, c=2.0):
    x = ctx.array(X[:rows, :cols].astype(dtype), dist=RowDist())
    y = ctx.zeros((rows, cols), dtype=dtype, dist=RowDist())
    before = count(reg, "affine", "miss")
    res = ctx.launch(k, grid=(rows, grid_cols), args={"x": x, "y": y},
                     scalars={"c": c})
    val = np.asarray(res["y"].value.astype(jnp.float32))
    want = X[:rows, :cols] * c + grid_cols
    return {"miss": count(reg, "affine", "miss") - before,
            "err": float(np.abs(val - want).max())}


def gemm(dist):
    a = ctx.array(A_np, dist=RowDist())
    b = ctx.array(B_np, dist=dist)
    c = ctx.zeros((m, m), dist=RowDist())
    before = count(reg, "gemm", "miss")
    res = ctx.launch(kg, grid=(m, m), args={"A": a, "B": b, "C": c})
    return {"miss": count(reg, "gemm", "miss") - before,
            "err": float(np.abs(np.asarray(res["C"].value)
                                - A_np @ B_np).max()),
            "pattern": ctx.records[-1].comm["B"].value}


got["base"] = affine()
got["base_again"] = affine()
got["grid"] = affine(grid_cols=8)
got["shape"] = affine(rows=128)
got["dtype"] = affine(dtype=jnp.bfloat16)
got["scalar"] = affine(c=3.0)
got["base_after"] = affine()
got["dist_base"] = gemm(RowDist())
got["distribution"] = gemm(ReplicatedDist())

# -- the least recently used program goes past the bound -------------------------
launch_mod._MESH_PROGRAMS = 2
reg = MetricsRegistry()
ctx = Context(mesh=mesh, registry=reg)
affine(c=2.0)
affine(c=5.0)
affine(c=2.0)  # a hit: c=5.0 is now the least recently used
affine(c=6.0)  # drops c=5.0
got["evicted"] = affine(c=5.0)  # drops c=2.0
got["kept"] = affine(c=6.0)
got["programs_kept"] = len(ctx._mesh_programs)

# -- the cache keeps no input alive ---------------------------------------------
reg = MetricsRegistry()
ctx = Context(mesh=mesh, registry=reg)
x = ctx.array(np.ones((64, 16), np.float32), dist=RowDist())
y = ctx.zeros((64, 16), dist=RowDist())
for _ in range(2):
    res = ctx.launch(k, grid=(64, 16), args={"x": x, "y": y},
                     scalars={"c": jnp.float32(2.0)})
refs = [weakref.ref(x), weakref.ref(x.value), weakref.ref(y)]
del x, y, res
gc.collect()
got["refs"] = {"alive": [r() is not None for r in refs],
               "hit": count(reg, "affine", "hit")}
print(json.dumps(got))
"""


@pytest.fixture(scope="module")
def got():
    out = run_with_devices(SNIPPET, n_devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("case,pattern", [
    ("halo", "halo"), ("gather", "gather"), ("reduce", "reduce"),
])
def test_cached_launch_matches_uncached(got, case, pattern):
    r = got[case]
    assert pattern in r["pattern"]
    assert (r["miss"], r["hit"]) == (2, 1)  # the uncached build is a miss
    assert r["hit_vs_miss"] == 0.0  # the same program, bit for bit
    # A separately compiled program may order a float sum differently.
    tol = 0.0 if case == "halo" else 1e-5 * r["scale"]
    assert r["hit_vs_uncached"] <= tol
    assert r["hit_vs_numpy"] <= 1e-5 * r["scale"]


@pytest.mark.parametrize("change", [
    "grid", "shape", "dtype", "scalar", "distribution",
])
def test_signature_change_is_a_miss_and_right(got, change):
    assert got["base"]["miss"] == 1 and got["base_again"]["miss"] == 0
    assert got[change]["miss"] == 1
    # small integers times 2 or 3 are exact; a gemm may round
    assert got[change]["err"] <= (1e-4 if change == "distribution" else 0.0)
    # every other signature stays cached
    assert got["base_after"]["miss"] == 0 and got["dist_base"]["miss"] == 1


def test_distribution_change_replans_the_argument(got):
    assert got["dist_base"]["pattern"] == "gather"
    assert got["distribution"]["pattern"] == "replicated"


def test_least_recently_used_program_is_dropped(got):
    assert got["evicted"] == {"miss": 1, "err": 0.0}
    assert got["kept"] == {"miss": 0, "err": 0.0}
    assert got["programs_kept"] == 2


def test_cache_holds_no_input_arrays(got):
    r = got["refs"]
    assert r["hit"] == 1  # the second launch reused the first's program
    assert r["alive"] == [False, False, False]
