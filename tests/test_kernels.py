"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True.

Every kernel in ``repro.kernels`` is validated against its ``ref.py`` oracle
across a sweep of shapes (odd sizes exercise the padding paths) and dtypes.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    attention_ref,
    black_scholes,
    black_scholes_ref,
    cluster_sums,
    cluster_sums_ref,
    correlate,
    correlate_ref,
    decode_attention,
    decode_attention_ref,
    flash_attention,
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
    rg_lru,
    rg_lru_ref,
    spmv_ell,
    spmv_ell_ref,
    wkv6,
    wkv6_ref,
)
from repro.kernels.md5.ref import md5_u32x2

RNG = np.random.RandomState(42)


def f32(*shape, scale=1.0):
    return jnp.asarray(RNG.randn(*shape).astype(np.float32) * scale)


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (100, 60, 130), (64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_sweep(m, k, n, dtype):
    a = f32(m, k).astype(dtype)
    b = f32(k, n).astype(dtype)
    got = gemm(a, b, block_m=128, block_n=128, block_k=128)
    want = gemm_ref(a, b)
    # f32: blocked K accumulation reorders sums vs the single-dot oracle.
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


# ---------------------------------------------------------------------------
# HotSpot stencil
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,block", [((64, 128), 16), ((100, 256), 32),
                                         ((33, 128), 32)])
def test_hotspot_sweep(shape, block):
    t = f32(*shape, scale=30.0) + 60.0
    p = f32(*shape, scale=0.5) ** 2
    got = hotspot_step(t, p, block_rows=block)
    want = hotspot_step_ref(t, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("rows,cols,block", [(32, 128, 8), (27, 128, 8)])
def test_hotspot_halo_rows_match_whole_grid(rows, cols, block):
    """Slabs stepped with their neighbours' rows as ``up``/``down`` (a
    halo exchange) reassemble the step of the whole grid."""
    t = f32(3 * rows, cols, scale=30.0) + 60.0
    p = f32(3 * rows, cols, scale=0.5) ** 2
    want = np.asarray(hotspot_step_ref(t, p))
    for lo in range(0, 3 * rows, rows):
        hi = lo + rows
        up = t[lo - 1:lo] if lo else None
        down = t[hi:hi + 1] if hi < 3 * rows else None
        got = hotspot_step(t[lo:hi], p[lo:hi], up=up, down=down,
                           block_rows=block)
        np.testing.assert_allclose(np.asarray(got), want[lo:hi],
                                   rtol=2e-5, atol=2e-4)


def test_hotspot_iterated_stable():
    t = f32(64, 128, scale=10.0) + 70.0
    p = jnp.abs(f32(64, 128, scale=0.3))
    for _ in range(5):
        t = hotspot_step(t, p, block_rows=32)
    assert bool(jnp.isfinite(t).all())


# ---------------------------------------------------------------------------
# Black-Scholes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [512, 1000, 8192])
def test_black_scholes_sweep(n):
    s = 5.0 + jnp.abs(f32(n)) * 25
    k = 1.0 + jnp.abs(f32(n)) * 99
    t = 0.25 + jnp.abs(f32(n)) * 9
    call, put = black_scholes(s, k, t, block=2048)
    call_r, put_r = black_scholes_ref(s, k, t)
    np.testing.assert_allclose(np.asarray(call), np.asarray(call_r),
                               rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(put), np.asarray(put_r),
                               rtol=1e-4, atol=2e-4)


def test_black_scholes_put_call_parity():
    n, r = 1024, 0.02
    s = 5.0 + jnp.abs(f32(n)) * 25
    k = 1.0 + jnp.abs(f32(n)) * 99
    t = 0.25 + jnp.abs(f32(n)) * 9
    call, put = black_scholes(s, k, t, riskfree=r)
    parity = np.asarray(call - put - (s - k * jnp.exp(-r * t)))
    np.testing.assert_allclose(parity, 0.0, atol=5e-4)


# ---------------------------------------------------------------------------
# K-Means
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,f", [(2048, 40, 4), (1000, 7, 4), (4096, 16, 8)])
def test_kmeans_sweep(n, k, f):
    pts = jnp.abs(f32(n, f))
    cen = jnp.abs(f32(k, f))
    s1, c1 = kmeans_assign_reduce(pts, cen, block=512)
    s2, c2 = kmeans_assign_reduce_ref(pts, cen)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-3)
    assert float(c1.sum()) == pytest.approx(n)


# ---------------------------------------------------------------------------
# SpMV (ELL)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,maxnnz", [(512, 8), (300, 16), (1024, 4)])
def test_spmv_sweep(n, maxnnz):
    data = RNG.rand(n, maxnnz).astype(np.float32)
    data *= RNG.rand(n, maxnnz) < 0.7
    cols = RNG.randint(0, n, (n, maxnnz)).astype(np.int32)
    x = RNG.rand(n).astype(np.float32)
    got = spmv_ell(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x),
                   block=128)
    want = spmv_ell_ref(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# MD5
# ---------------------------------------------------------------------------


def test_md5_matches_hashlib():
    import hashlib
    import struct

    for v in (0, 1, 255, 123456, 2**31):
        w0 = np.uint32(v & 0xFFFFFFFF)
        w1 = np.uint32((v ^ 0x9E3779B9) & 0xFFFFFFFF)
        a, b, c, d = md5_u32x2(jnp.asarray([w0]), jnp.asarray([w1]))
        got = struct.pack("<IIII", int(a[0]), int(b[0]), int(c[0]), int(d[0]))
        want = hashlib.md5(struct.pack("<II", w0, w1)).digest()
        assert got == want


@pytest.mark.parametrize("target_key", [0, 77, 511, 1500])
def test_md5_search(target_key):
    w0 = np.uint32(target_key)
    w1 = np.uint32(target_key ^ 0x9E3779B9)
    a, b, c, d = md5_u32x2(jnp.asarray([w0]), jnp.asarray([w1]))
    target = (int(a[0]), int(b[0]), int(c[0]), int(d[0]))
    assert int(md5_search(2048, target, block=512)) == target_key
    assert int(md5_search_ref(2048, target)) == target_key


def test_md5_search_no_match():
    assert int(md5_search(256, (1, 2, 3, 4), block=128)) == 256


# ---------------------------------------------------------------------------
# N-Body
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,bi,bj", [(256, 128, 128), (300, 128, 64),
                                     (128, 128, 128)])
def test_nbody_sweep(n, bi, bj):
    posm = np.abs(RNG.rand(n, 4).astype(np.float32))
    posm[:, 3] += 0.5
    got = nbody_forces(jnp.asarray(posm), block_i=bi, block_j=bj)
    want = nbody_forces_ref(jnp.asarray(posm))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_nbody_ref_targets_are_rows_of_the_whole():
    posm = RNG.rand(96, 4).astype(np.float32)
    rows = np.array([0, 5, 50, 95])
    whole = nbody_forces_ref(jnp.asarray(posm))
    some = nbody_forces_ref(jnp.asarray(posm), targets=jnp.asarray(posm[rows]))
    np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                               rtol=1e-6, atol=1e-6)


def test_nbody_momentum_conservation():
    """Equal masses: total force ≈ 0 (Newton's third law)."""
    n = 128
    posm = RNG.rand(n, 4).astype(np.float32)
    posm[:, 3] = 1.0
    acc = np.asarray(nbody_forces(jnp.asarray(posm), block_i=64, block_j=64))
    np.testing.assert_allclose(acc.sum(axis=0), 0.0, atol=2e-2)


# ---------------------------------------------------------------------------
# Correlator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c,t,a", [(4, 100, 16), (2, 64, 8), (1, 200, 32)])
def test_correlator_sweep(c, t, a):
    s = f32(c, t, a, 2, scale=0.5)
    got = correlate(s, block_t=32)
    want = correlate_ref(s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_correlator_hermitian():
    s = f32(2, 64, 8, 2, scale=0.5)
    v = np.asarray(correlate(s, block_t=32))
    # V[i,j] = conj(V[j,i])
    np.testing.assert_allclose(v[..., 0], v[..., 0].transpose(0, 2, 1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(v[..., 1], -v[..., 1].transpose(0, 2, 1),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Co-clustering
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,m,R,C", [(500, 64, 5, 4), (256, 128, 8, 8),
                                     (40, 1100, 3, 5)])
def test_cluster_sums_sweep(n, m, R, C):
    z = jnp.abs(f32(n, m))
    ra = jnp.asarray(RNG.randint(0, R, n).astype(np.int32))
    ca = jnp.asarray(RNG.randint(0, C, m).astype(np.int32))
    got = cluster_sums(z, ra, ca, R, C, block_n=128)
    want = cluster_sums_ref(z, ra, ca, R, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    # total mass is conserved
    np.testing.assert_allclose(float(got.sum()), float(z.sum()), rtol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,s,d,window", [
    (2, 4, 4, 128, 64, None),   # MHA
    (1, 8, 2, 256, 64, None),   # GQA
    (1, 4, 1, 128, 32, None),   # MQA
    (1, 4, 1, 128, 32, 64),     # sliding window
    (2, 4, 2, 100, 32, None),   # unaligned seq (padding path)
])
def test_flash_attention_sweep(b, hq, hkv, s, d, window):
    q = f32(b, hq, s, d, scale=0.5)
    k = f32(b, hkv, s, d, scale=0.5)
    v = f32(b, hkv, s, d, scale=0.5)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q = f32(1, 4, 128, 64, scale=0.5).astype(jnp.bfloat16)
    k = f32(1, 4, 128, 64, scale=0.5).astype(jnp.bfloat16)
    v = f32(1, 4, 128, 64, scale=0.5).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,t,d", [(2, 8, 2, 512, 64),
                                          (1, 4, 4, 300, 32),
                                          (2, 4, 1, 256, 64)])
def test_decode_attention_sweep(b, hq, hkv, t, d):
    q = f32(b, hq, d, scale=0.5)
    k = f32(b, hkv, t, d, scale=0.5)
    v = f32(b, hkv, t, d, scale=0.5)
    kv_len = jnp.asarray(RNG.randint(t // 2, t, b), jnp.int32)
    got, lse = decode_attention(q, k, v, kv_len=kv_len, block_k=128,
                                with_lse=True)
    want, lse_r = decode_attention_ref(q, k, v, kv_len=kv_len, with_lse=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=1e-4, atol=1e-4)


def test_decode_lse_partial_combine():
    """Flash-decode: combining two half-cache partials via LSE must equal
    attention over the full cache (the SP correctness property)."""
    b, h, t, d = 1, 4, 256, 32
    q = f32(b, h, d, scale=0.5)
    k = f32(b, h, t, d, scale=0.5)
    v = f32(b, h, t, d, scale=0.5)
    full = decode_attention_ref(q, k, v)
    o1, l1 = decode_attention_ref(q, k[:, :, :128], v[:, :, :128],
                                  with_lse=True)
    o2, l2 = decode_attention_ref(q, k[:, :, 128:], v[:, :, 128:],
                                  with_lse=True)
    m = np.maximum(np.asarray(l1), np.asarray(l2))
    w1 = np.exp(np.asarray(l1) - m)[..., None]
    w2 = np.exp(np.asarray(l2) - m)[..., None]
    combined = (np.asarray(o1) * w1 + np.asarray(o2) * w2) / (w1 + w2)
    np.testing.assert_allclose(combined, np.asarray(full), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 / RG-LRU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,t,dk,dv,bt", [(2, 2, 64, 16, 16, 16),
                                            (1, 4, 50, 8, 8, 16)])
def test_wkv6_sweep(b, h, t, dk, dv, bt):
    r = f32(b, h, t, dk, scale=0.3)
    k = f32(b, h, t, dk, scale=0.3)
    v = f32(b, h, t, dv, scale=0.3)
    w = jnp.exp(-jnp.exp(f32(b, h, t, dk)))
    u = f32(h, dk, scale=0.3)
    got, sT = wkv6(r, k, v, w, u, block_t=bt, return_state=True)
    want, sT_r = wkv6_ref(r, k, v, w, u, return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_r),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_state_chaining():
    """Processing [0:T] at once == [0:T/2] then [T/2:T] with carried state."""
    b, h, t, dk, dv = 1, 2, 32, 8, 8
    r = f32(b, h, t, dk, scale=0.3)
    k = f32(b, h, t, dk, scale=0.3)
    v = f32(b, h, t, dv, scale=0.3)
    w = jnp.exp(-jnp.exp(f32(b, h, t, dk)))
    u = f32(h, dk, scale=0.3)
    full = wkv6_ref(r, k, v, w, u)
    h1, s1 = wkv6_ref(r[:, :, :16], k[:, :, :16], v[:, :, :16],
                      w[:, :, :16], u, return_state=True)
    h2 = wkv6_ref(r[:, :, 16:], k[:, :, 16:], v[:, :, 16:],
                  w[:, :, 16:], u, initial_state=s1)
    got = jnp.concatenate([h1, h2], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,t,d,bt,bd", [(2, 96, 256, 32, 128),
                                         (1, 64, 64, 16, 64),
                                         (2, 50, 100, 16, 64)])
def test_rg_lru_sweep(b, t, d, bt, bd):
    la = -jnp.abs(f32(b, t, d, scale=0.1))
    gx = f32(b, t, d)
    h0 = f32(b, d, scale=0.5)
    got, hT = rg_lru(la, gx, h0, block_t=bt, block_d=bd, return_state=True)
    want, hT_r = rg_lru_ref(la, gx, h0, return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_r),
                               rtol=1e-4, atol=1e-4)


def test_rg_lru_decay_bounds():
    """With log_a = 0 (a=1, beta=0) the state is constant; with very negative
    log_a (a≈0) h_t ≈ gx_t."""
    b, t, d = 1, 16, 32
    gx = f32(b, t, d)
    h0 = f32(b, d)
    out = rg_lru_ref(jnp.zeros((b, t, d)), gx, h0)
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(np.asarray(h0)[:, None], out.shape),
        atol=1e-6,
    )
    out2 = rg_lru_ref(jnp.full((b, t, d), -50.0), gx, h0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(gx), atol=1e-5)
