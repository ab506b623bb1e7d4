"""Multi-device integration: shard_map lowering of Lightning launches,
collective matmuls, elastic resharding — run in subprocesses with 8 fake
host devices (the main process keeps the single real device)."""

import pytest

from _subproc import run_with_devices


@pytest.mark.slow
def test_lightning_launch_patterns_multidevice():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import *

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
ctx = Context(mesh=mesh, devices_per_node=4)
rng = np.random.RandomState(0)
n = 1024

# stencil: halo exchange
def stencil_body(views, info):
    x = views["input"]
    return {"output": (x[:-2] + x[1:-1] + x[2:]) / 3.0}
k = KernelDef.define("stencil", stencil_body,
                     "global i => read input[i-1:i+1], write output[i]")
x_np = rng.rand(n).astype(np.float32)
inp = ctx.array(x_np, dist=StencilDist(n//8, 1), name="input")
out = ctx.zeros((n,), dist=BlockDist(n//8), name="output")
res = ctx.launch(k, grid=(n,), args={"input": inp, "output": out})
pad = np.pad(x_np, 1)
np.testing.assert_allclose(np.asarray(res["output"].value),
                           (pad[:-2]+pad[1:-1]+pad[2:])/3.0, rtol=1e-6)
assert ctx.records[-1].comm["input"].value == "halo"

# gemm: all-gather of B
def gemm_body(views, info):
    return {"C": views["A"] @ views["B"]}
kg = KernelDef.define("gemm", gemm_body,
    "global [i, j] => read A[i,:], read B[:,j], write C[i,j]")
m = 256
A = ctx.array(rng.rand(m,m).astype(np.float32), dist=RowDist(), name="A")
B = ctx.array(rng.rand(m,m).astype(np.float32), dist=RowDist(), name="B")
C = ctx.zeros((m,m), dist=RowDist(), name="C")
res = ctx.launch(kg, grid=(m,m), args={"A": A, "B": B, "C": C})
np.testing.assert_allclose(np.asarray(res["C"].value),
    np.asarray(A.value) @ np.asarray(B.value), rtol=1e-4)
assert ctx.records[-1].comm["B"].value == "gather"

# reduction
def colsum_body(views, info):
    return {"s": views["A"].sum(axis=0)}
kr = KernelDef.define("colsum", colsum_body,
    "global [i, j] => read A[i,j], reduce(+) s[j]")
A2 = ctx.array(rng.rand(512, 32).astype(np.float32), dist=RowDist(), name="A")
s = ctx.zeros((32,), dist=ReplicatedDist(), name="s")
res = ctx.launch(kr, grid=(512, 32), args={"A": A2, "s": s})
np.testing.assert_allclose(np.asarray(res["s"].value),
    np.asarray(A2.value).sum(axis=0), rtol=1e-5)
print("LAUNCH-OK")
""")
    assert "LAUNCH-OK" in out


@pytest.mark.slow
def test_placement_puts_each_share_on_its_device():
    """Host data and constant fills land on a 4-device mesh shard by shard:
    each device holds only its rows, and a launch over them equals the
    same launch on one device."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import *

mesh = jax.make_mesh((4,), ("data",))
ctx = Context(mesh=mesh)
x = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
arrays = {
    "x": (ctx.array(x, dist=RowDist(), name="x"), x),
    "z": (ctx.zeros((64, 8), dist=RowDist(), name="z"), np.zeros((64, 8))),
    "f": (ctx.full((64, 8), 2.5, dist=RowDist(), name="f"),
          np.full((64, 8), 2.5)),
}
for name, (arr, want) in arrays.items():
    shards = arr.value.addressable_shards
    assert {s.device for s in shards} == set(mesh.devices.flat), name
    for s in shards:
        assert s.data.shape == (16, 8), (name, s.data.shape)
        assert s.data.devices() == {s.device}, name
        np.testing.assert_array_equal(np.asarray(s.data), want[s.index])

k = KernelDef.define(
    "axpy", lambda v, _: {"z": 3.0 * v["x"] + v["f"]},
    "global [i, j] => read x[i,j], read f[i,j], write z[i,j]")
res = ctx.launch(k, grid=(64, 8), args={n: a for n, (a, _) in arrays.items()})
one = Context()
res1 = one.launch(k, grid=(64, 8), args={
    "x": one.array(x), "f": one.full((64, 8), 2.5), "z": one.zeros((64, 8))})
np.testing.assert_array_equal(np.asarray(res["z"].value),
                              np.asarray(res1["z"].value))
np.testing.assert_array_equal(np.asarray(res["z"].value), 3.0 * x + 2.5)
print("PLACEMENT-OK")
""", n_devices=4)
    assert "PLACEMENT-OK" in out


@pytest.mark.slow
def test_collective_matmuls_multidevice():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import (
    ring_allgather_matmul, hierarchical_grad_allreduce)

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.RandomState(0)

# ring all-gather matmul == full matmul (contraction sharded over data)
x = rng.rand(16, 64).astype(np.float32)
w = rng.rand(64, 32).astype(np.float32)
ring = shard_map(
    partial(ring_allgather_matmul, axis_name="data"),
    mesh=mesh, in_specs=(P(None, "data"), P("data", None)),
    out_specs=P(), check_vma=False)
got = ring(jnp.asarray(x), jnp.asarray(w))
np.testing.assert_allclose(np.asarray(got), x @ w, rtol=1e-4)

# hierarchical grad allreduce == psum
g = {"w": jnp.asarray(rng.rand(8, 4).astype(np.float32))}
def ref_fn(t):
    return jax.tree.map(lambda v: jax.lax.psum(v, ("data", "pod")), t)
def hier_fn(t):
    return hierarchical_grad_allreduce(t, intra_axes=("data",),
                                       inter_axes=("pod",))
for fn in (ref_fn, hier_fn):
    pass
ref = shard_map(ref_fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False)(g)
hier = shard_map(hier_fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
                 check_vma=False)(g)
np.testing.assert_allclose(np.asarray(ref["w"]), np.asarray(hier["w"]),
                           rtol=1e-5)
print("COLLECTIVES-OK")
""")
    assert "COLLECTIVES-OK" in out


@pytest.mark.slow
def test_elastic_reshard_across_meshes():
    """Checkpoint on a (4,2) mesh, restore onto (2,4) and single device."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt import CheckpointManager
from repro.configs import get_smoke_config
from repro.train.train_loop import init_train_state, train_state_specs
from repro.launch.rules import rules_for

cfg = get_smoke_config("phi3-mini-3.8b")
tmp = tempfile.mkdtemp()

mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,)*2)
rules_a = rules_for(cfg, mesh_a, "tp")
specs_a = train_state_specs(cfg, rules_a)
state = init_train_state(jax.random.key(0), cfg)
state = jax.device_put(state, jax.tree.map(
    lambda s: NamedSharding(mesh_a, s), specs_a,
    is_leaf=lambda x: isinstance(x, P)))
mgr = CheckpointManager(tmp)
mgr.save(3, state, blocking=True)

# restore onto a DIFFERENT mesh shape
mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,)*2)
rules_b = rules_for(cfg, mesh_b, "tp")
specs_b = train_state_specs(cfg, rules_b)
template = jax.eval_shape(lambda: init_train_state(jax.random.key(0), cfg))

from repro.ckpt.checkpoint import _flatten_with_paths
flat_specs = dict(zip(
    [k for k, _ in _flatten_with_paths(template)],
    [s for _, s in _flatten_with_paths(jax.tree.map(
        lambda x: x, specs_b, is_leaf=lambda x: isinstance(x, P)))],
))
def put(key, arr):
    return jax.device_put(arr, NamedSharding(mesh_b, flat_specs[key]))
restored, meta = mgr.restore(template, put=put)
assert meta["step"] == 3
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# and plain single-device restore
restored1, _ = mgr.restore(template)
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored1)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC-OK")
""")
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_compressed_psum_multidevice():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum, ErrorFeedback

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.RandomState(0)
g_global = rng.randn(8, 64).astype(np.float32)

def body(g):
    out, _ = compressed_psum({"g": g}, "data", None)
    return out["g"]

fn = shard_map(body, mesh=mesh, in_specs=(P("data", None),),
               out_specs=P(None), check_vma=False)
got = np.asarray(fn(jnp.asarray(g_global)))[0]
want = g_global.sum(axis=0)
# int8 quantization: bounded relative error vs true sum
scale = np.abs(g_global + 0).max() / 127
np.testing.assert_allclose(got, want, atol=scale * 8 * 1.01 + 1e-5)
print("COMPRESS-OK")
""")
    assert "COMPRESS-OK" in out
