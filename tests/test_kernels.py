"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpreted off a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.mamba2_ssd import ref as ssd_ref
from repro.kernels.mamba2_ssd.mamba2_ssd import ssd_pallas
from repro.kernels.moe_gmm.moe_gmm import gmm
from repro.kernels.moe_gmm.ref import gmm_reference
from repro.kernels.mpnn_mp.mpnn_mp import message_pass_pallas
from repro.kernels.mpnn_mp.ref import message_pass_reference
from repro.kernels.rwkv6_scan import ref as wkv_ref
from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_pallas

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,KVH,hd,causal,window,softcap,off",
    [
        (2, 128, 128, 4, 2, 32, True, None, None, 0),
        (1, 256, 256, 4, 4, 64, True, 64, None, 0),
        (2, 128, 128, 8, 2, 32, True, None, 50.0, 0),
        (1, 128, 256, 4, 2, 32, True, None, None, 128),
        (2, 128, 128, 4, 1, 32, False, None, None, 0),
        (1, 64, 64, 2, 2, 128, True, 32, 30.0, 0),
    ])
def test_flash_attention(B, Sq, Sk, H, KVH, hd, causal, window, softcap,
                         off, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KVH, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KVH, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=off,
                          block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=off)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,P,G,N,Q", [
    (2, 256, 4, 32, 1, 16, 64),
    (1, 128, 8, 64, 2, 32, 128),
    (2, 256, 4, 32, 4, 16, 64),
])
def test_mamba2_ssd_kernel(B, L, H, P, G, N, Q, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    la = (-jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.3)
    b = jax.random.normal(ks[2], (B, L, G, N), dtype)
    c = jax.random.normal(ks[3], (B, L, G, N), dtype)
    s0 = jax.random.normal(ks[4], (B, H, P, N))
    y1, s1 = ssd_pallas(x, la, b, c, s0, chunk=Q)
    y2, s2 = ssd_ref.ssd_naive(x, la, b, c, s0)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=tol, atol=tol)


def test_mamba2_chunked_ref_matches_naive():
    ks = jax.random.split(KEY, 5)
    B, L, H, P, G, N = 2, 256, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (B, L, H)))  # strong decay
    b = jax.random.normal(ks[2], (B, L, G, N))
    c = jax.random.normal(ks[3], (B, L, G, N))
    s0 = jax.random.normal(ks[4], (B, H, P, N))
    y1, s1 = ssd_ref.ssd_chunked(x, la, b, c, s0, chunk=32)
    y2, s2 = ssd_ref.ssd_naive(x, la, b, c, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,L,H,K,V,Q", [
    (2, 128, 4, 32, 32, 64),
    (1, 128, 2, 64, 64, 32),
])
def test_rwkv6_kernel(B, L, H, K, V, Q):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, L, H, K))
    k = jax.random.normal(ks[1], (B, L, H, K))
    v = jax.random.normal(ks[2], (B, L, H, V))
    lw = -jnp.abs(jax.random.normal(ks[3], (B, L, H, K))) * 2.0
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, K, V))
    y1, s1 = wkv6_pallas(r, k, v, lw, u, s0, chunk=Q)
    y2, s2 = wkv_ref.wkv6_naive(r, k, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_chunked_ref_strong_decay_stable():
    """The hybrid chunked form must survive decay regimes where the naive
    parallel form overflows (|log w| large)."""
    ks = jax.random.split(KEY, 5)
    B, L, H, K, V = 1, 256, 2, 16, 16
    r = jax.random.normal(ks[0], (B, L, H, K))
    k = jax.random.normal(ks[1], (B, L, H, K))
    v = jax.random.normal(ks[2], (B, L, H, V))
    lw = -jnp.abs(jax.random.normal(ks[3], (B, L, H, K))) * 11.9
    u = jax.random.normal(ks[4], (H, K))
    y1, s1 = wkv_ref.wkv6_chunked(r, k, v, lw, u, chunk=64)
    y2, s2 = wkv_ref.wkv6_naive(r, k, v, lw, u)
    assert np.all(np.isfinite(np.asarray(y1)))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [(4, 128, 256, 512), (8, 64, 128, 128)])
def test_gmm_kernel(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 2)
    xe = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    o1 = gmm(xe, w, block_c=64, block_f=128, block_d=128)
    o2 = gmm_reference(xe, w)
    tol = 1e-3 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,N,Hd", [(3, 16, 32), (2, 8, 64)])
def test_mpnn_kernel(B, N, Hd):
    ks = jax.random.split(KEY, 3)
    h = jax.random.normal(ks[0], (B, N, Hd))
    e = jax.random.normal(ks[1], (B, N, N, Hd, Hd)) * 0.1
    adj = (jax.random.uniform(ks[2], (B, N, N)) > 0.5).astype(jnp.float32)
    m1 = message_pass_pallas(h, e, adj)
    m2 = message_pass_reference(h, e, adj)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2),
                               rtol=1e-4, atol=1e-4)
