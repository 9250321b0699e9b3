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
