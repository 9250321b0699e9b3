"""Pallas kernels vs pure-jnp oracles, interpret=True shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ref import ssd_chunk_ref, ssd_full_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_chunk
from repro.kernels.tiled_matmul.ref import matmul_ref
from repro.kernels.tiled_matmul.tiled_matmul import tiled_matmul
from repro.kernels import ops


@pytest.mark.parametrize("b,h,s,d", [(1, 1, 128, 64), (2, 3, 256, 64),
                                     (1, 2, 384, 128), (2, 1, 256, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, h, s, d, dtype):
    ks = jax.random.split(jax.random.key(b * 100 + s), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32).astype(dtype)
               for kk in ks)
    out = flash_attention(q, k, v, interpret=True)
    ref = attention_ref(q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [64, 128, 256])
def test_flash_attention_sliding_window(window):
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.key(window), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in ks)
    out = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("block", [64, 128])
def test_flash_attention_block_invariance(block):
    """Output must not depend on the blocking."""
    b, h, s, d = 1, 1, 256, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in ks)
    a = flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)
    bfull = flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bfull), atol=1e-5)


@pytest.mark.parametrize("bsz,nc,l,h,p,n", [(1, 2, 64, 2, 32, 16),
                                            (2, 4, 32, 4, 16, 8)])
def test_ssd_chunk_kernel(bsz, nc, l, h, p, n):
    ks = jax.random.split(jax.random.key(l + n), 5)
    x = jax.random.normal(ks[0], (bsz, nc, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, nc, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (bsz, nc, l, n))
    C = jax.random.normal(ks[4], (bsz, nc, l, n))
    dA = dt * A[None, None, None, :]
    y, st = ssd_chunk(x, dA, dt, B, C, interpret=True)
    yr, str_ = ssd_chunk_ref(x, dA, dt, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), rtol=2e-4, atol=2e-4)


def test_ssd_full_scan_vs_model_layer():
    bsz, nc, l, h, p, n = 1, 4, 32, 2, 16, 8
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (bsz, nc, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, nc, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (bsz, nc, l, n))
    C = jax.random.normal(ks[4], (bsz, nc, l, n))
    y, final = ops.ssd_scan(x, dt, A, B, C)
    yr, fr = ssd_full_ref(x.reshape(bsz, nc * l, h, p),
                          dt.reshape(bsz, nc * l, h), A,
                          B.reshape(bsz, nc * l, 1, n),
                          C.reshape(bsz, nc * l, 1, n), l)
    np.testing.assert_allclose(np.asarray(y.reshape(bsz, nc * l, h, p)),
                               np.asarray(yr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final.transpose(0, 1, 3, 2)),
                               np.asarray(fr), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul(m, k, n, dtype):
    a = jax.random.normal(jax.random.key(m + n), (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.key(k), (k, n), jnp.float32).astype(dtype)
    out = tiled_matmul(a, b, interpret=True)
    ref = matmul_ref(a, b)
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_tiled_matmul_block_invariance():
    a = jax.random.normal(jax.random.key(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)
    o1 = tiled_matmul(a, b, block_m=64, block_n=64, block_k=64, interpret=True)
    o2 = tiled_matmul(a, b, block_m=128, block_n=128, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# fulcrum engine kernels: max-plus scan + lane sort. These run in float64
# under enable_x64 (the engine's working precision) and are checked against
# BOTH the lax.associative_scan oracle (ref.py) and an independent scalar
# replay of the managed recurrence — tolerances per docs/exactness.md.
# ---------------------------------------------------------------------------

from repro.kernels.fulcrum.lane_sort import lane_sort
from repro.kernels.fulcrum.maxplus_scan import maxplus_scan
from repro.kernels.fulcrum.ref import (lane_sort_ref, lane_violations_ref,
                                       maxplus_scan_ref)

_enable_x64 = jax.enable_x64
ENG_TOL = dict(rtol=1e-9, atol=1e-8)


def _maxplus_case(rng, lanes, kmax):
    """Ragged lanes padded the engine's way (+inf ready / 0 exec), with
    random nonzero clocks (backlog carryover) and +inf t_tr / tau_cap
    (no-training / uncapped lanes)."""
    sizes = rng.integers(0, kmax + 1, lanes)
    K = max(int(sizes.max(initial=0)), 1)
    ready = np.full((lanes, K), np.inf)
    exec_t = np.zeros((lanes, K))
    for i, nsz in enumerate(sizes):
        ready[i, :nsz] = np.sort(rng.uniform(0.0, 5.0, nsz))
        exec_t[i, :nsz] = rng.uniform(0.01, 0.5, nsz)
    t_tr = np.where(rng.random(lanes) < 0.3, np.inf,
                    rng.uniform(0.05, 0.5, lanes))
    cap = np.where(rng.random(lanes) < 0.5, np.inf,
                   rng.integers(0, 5, lanes).astype(np.float64))
    clock = np.where(rng.random(lanes) < 0.5, 0.0,
                     rng.uniform(0.0, 2.0, lanes))
    return ready, exec_t, t_tr, cap, clock, sizes


def _maxplus_scalar(ready, exec_t, t_tr, cap, clock):
    """Independent oracle: the managed recurrence replayed event-by-event in
    Python (completion c_k = max(c_{k-1}, ready_k) + e_k, fills clipped to
    the cap), skipping padded (+inf ready) events for the fill count."""
    lanes, K = ready.shape
    c = np.empty((lanes, K))
    fills = np.zeros(lanes)
    for i in range(lanes):
        t = clock[i]
        for k in range(K):
            if np.isfinite(ready[i, k]):
                gap = ready[i, k] - t
                fills[i] += min(max(np.floor(gap / t_tr[i]), 0.0), cap[i])
            t = max(t, ready[i, k]) + exec_t[i, k]
            c[i, k] = t
    return c, fills


@pytest.mark.parametrize("seed,lanes,kmax", [(0, 1, 16), (1, 7, 33),
                                             (2, 64, 5), (3, 17, 120)])
def test_maxplus_scan_matches_ref_and_scalar(seed, lanes, kmax):
    rng = np.random.default_rng(seed)
    ready, exec_t, t_tr, cap, clock, sizes = _maxplus_case(rng, lanes, kmax)
    with _enable_x64():
        c, fills = maxplus_scan(jnp.asarray(ready), jnp.asarray(exec_t),
                                jnp.asarray(t_tr), jnp.asarray(cap),
                                jnp.asarray(clock), interpret=True)
        cr, fr = maxplus_scan_ref(jnp.asarray(ready), jnp.asarray(exec_t),
                                  jnp.asarray(t_tr), jnp.asarray(cap),
                                  jnp.asarray(clock))
    c, fills = np.asarray(c), np.asarray(fills)
    cr, fr = np.asarray(cr), np.asarray(fr)
    cs, fs = _maxplus_scalar(ready, exec_t, t_tr, cap, clock)
    for i, nsz in enumerate(sizes):
        np.testing.assert_allclose(c[i, :nsz], cr[i, :nsz], **ENG_TOL)
        np.testing.assert_allclose(c[i, :nsz], cs[i, :nsz], **ENG_TOL)
    np.testing.assert_allclose(fills, fr, **ENG_TOL)
    assert np.all(np.abs(fills - fs) <= 2)     # floor-boundary slack


@pytest.mark.parametrize("bl", [1, 3, 8, 64])
def test_maxplus_scan_block_invariance(bl):
    """Per-lane arithmetic is independent of the lane blocking — results
    must be bitwise identical whatever block_lanes is."""
    rng = np.random.default_rng(42)
    ready, exec_t, t_tr, cap, clock, _ = _maxplus_case(rng, 13, 40)
    with _enable_x64():
        args = (jnp.asarray(ready), jnp.asarray(exec_t), jnp.asarray(t_tr),
                jnp.asarray(cap), jnp.asarray(clock))
        c_a, f_a = maxplus_scan(*args, block_lanes=bl, interpret=True)
        c_b, f_b = maxplus_scan(*args, block_lanes=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(c_a), np.asarray(c_b))
        np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_b))


def test_maxplus_scan_empty_edges():
    with _enable_x64():
        c, f = maxplus_scan(jnp.zeros((0, 4)), jnp.zeros((0, 4)),
                            jnp.zeros((0,)), jnp.zeros((0,)),
                            jnp.zeros((0,)), interpret=True)
    assert np.asarray(c).shape == (0, 4) and np.asarray(f).shape == (0,)


def _sort_case(rng, lanes, reqs):
    mat = np.full((lanes, reqs), np.inf)
    for i in range(lanes):
        nsz = int(rng.integers(0, reqs + 1))
        mat[i, :nsz] = rng.uniform(1e-4, 10.0, nsz)
    return mat


@pytest.mark.parametrize("seed,lanes,reqs", [(0, 1, 1), (1, 9, 17),
                                             (2, 33, 64), (3, 8, 100)])
def test_lane_sort_exact_vs_numpy(seed, lanes, reqs):
    """Sorting permutes values — the sorted matrix must be *equal* to
    NumPy's sort, not merely close (and to the jnp oracle)."""
    rng = np.random.default_rng(50 + seed)
    mat = _sort_case(rng, lanes, reqs)
    budgets = rng.uniform(0.1, 5.0, lanes)
    with _enable_x64():
        srt, viol = lane_sort(jnp.asarray(mat), jnp.asarray(budgets),
                              interpret=True)
        ref = lane_sort_ref(jnp.asarray(mat))
        vref = lane_violations_ref(jnp.asarray(mat), jnp.asarray(budgets))
    np.testing.assert_array_equal(np.asarray(srt), np.sort(mat, axis=1))
    np.testing.assert_array_equal(np.asarray(srt), np.asarray(ref))
    want = [(np.isfinite(mat[i]) & (mat[i] > budgets[i])).sum()
            for i in range(lanes)]
    np.testing.assert_array_equal(np.asarray(viol), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(viol), np.asarray(vref))


@pytest.mark.parametrize("bl", [1, 5, 256])
def test_lane_sort_block_invariance_and_sorted_only(bl):
    rng = np.random.default_rng(77)
    mat = _sort_case(rng, 11, 23)
    with _enable_x64():
        a = lane_sort(jnp.asarray(mat), block_lanes=bl, interpret=True)
        b = lane_sort(jnp.asarray(mat), block_lanes=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.sort(mat, axis=1))
