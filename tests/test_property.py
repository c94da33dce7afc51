"""Hypothesis property tests on system invariants."""
import pytest

pytest.importorskip("hypothesis")

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import ResourceTracker
from repro.core.value_server import Proxy, ValueServer
from repro.kernels.mamba2_ssd import ref as ssd_ref
from repro.models.attention import mha_reference

SETTINGS = dict(max_examples=20, deadline=None)


# ---------------------------------------------------------------------------
# attention invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    B=st.integers(1, 2), S=st.sampled_from([16, 32, 64]),
    H=st.sampled_from([2, 4]), G=st.sampled_from([1, 2]),
    hd=st.sampled_from([8, 16]),
    chunk=st.sampled_from([8, 16, 64]),
    seed=st.integers(0, 2**16),
)
def test_attention_chunking_invariance(B, S, H, G, hd, chunk, seed):
    """Blockwise online-softmax result is independent of chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    KVH = H // G
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KVH, hd))
    v = jax.random.normal(ks[2], (B, S, KVH, hd))
    o1 = mha_reference(q, k, v, causal=True, chunk_q=chunk, chunk_k=chunk)
    o2 = mha_reference(q, k, v, causal=True, chunk_q=S, chunk_k=S)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


@settings(**SETTINGS)
@given(S=st.sampled_from([16, 32]), seed=st.integers(0, 2**16))
def test_attention_causality(S, seed):
    """Output at position i is unaffected by tokens at positions > i."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, H, hd = 1, 2, 8
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    o1 = mha_reference(q, k, v, causal=True, chunk_q=16, chunk_k=16)
    # perturb the future wildly
    k2 = k.at[:, S // 2:].add(100.0)
    v2 = v.at[:, S // 2:].add(-50.0)
    o2 = mha_reference(q, k2, v2, causal=True, chunk_q=16, chunk_k=16)
    np.testing.assert_allclose(np.asarray(o1[:, :S // 2]),
                               np.asarray(o2[:, :S // 2]),
                               rtol=1e-5, atol=1e-5)


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**16))
def test_attention_softmax_convexity(seed):
    """Each output row is a convex combination of V rows: it lies within
    the per-channel [min, max] envelope of the visible values."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, S, H, hd = 1, 32, 2, 8
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    o = np.asarray(mha_reference(q, k, v, causal=False,
                                 chunk_q=16, chunk_k=16))
    vmin = np.asarray(jnp.min(v, axis=1))[:, None]
    vmax = np.asarray(jnp.max(v, axis=1))[:, None]
    assert np.all(o >= vmin - 1e-4) and np.all(o <= vmax + 1e-4)


# ---------------------------------------------------------------------------
# SSD invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(seed=st.integers(0, 2**16), Q=st.sampled_from([8, 16, 32]))
def test_ssd_chunk_invariance(seed, Q):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, L, H, P, G, N = 1, 64, 2, 8, 1, 4
    x = jax.random.normal(ks[0], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (B, L, H)))
    b = jax.random.normal(ks[2], (B, L, G, N))
    c = jax.random.normal(ks[3], (B, L, G, N))
    s0 = jax.random.normal(ks[4], (B, H, P, N))
    y1, s1 = ssd_ref.ssd_chunked(x, la, b, c, s0, chunk=Q)
    y2, s2 = ssd_ref.ssd_naive(x, la, b, c, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**16))
def test_ssd_linearity_in_x(seed):
    """The SSD scan is linear in x (fixed decay/b/c)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, L, H, P, G, N = 1, 32, 2, 4, 1, 4
    x1 = jax.random.normal(ks[0], (B, L, H, P))
    x2 = jax.random.normal(ks[1], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[2], (B, L, H)))
    b = jax.random.normal(ks[3], (B, L, G, N))
    c = jax.random.normal(ks[4], (B, L, G, N))
    y1, _ = ssd_ref.ssd_naive(x1, la, b, c)
    y2, _ = ssd_ref.ssd_naive(x2, la, b, c)
    y12, _ = ssd_ref.ssd_naive(x1 + 2.0 * x2, la, b, c)
    np.testing.assert_allclose(np.asarray(y12), np.asarray(y1 + 2.0 * y2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# core invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(
    pools=st.dictionaries(st.sampled_from(["a", "b", "c"]),
                          st.integers(0, 16), min_size=2),
    moves=st.lists(st.tuples(st.sampled_from(["a", "b", "c"]),
                             st.sampled_from(["a", "b", "c"]),
                             st.integers(0, 8)), max_size=8),
)
def test_resource_total_conserved(pools, moves):
    rt = ResourceTracker(dict(pools))
    total = sum(pools.values())
    for src, dst, n in moves:
        if src in pools and dst in pools and src != dst:
            rt.reallocate(src, dst, min(n, rt.allocation(src)))
    assert sum(rt.allocation(p) for p in pools) == total


@settings(**SETTINGS)
@given(data=st.binary(min_size=0, max_size=4096))
def test_value_server_roundtrip(data):
    vs = ValueServer()
    key = vs.put(data)
    assert vs.get(key) == data
    p = Proxy(key, len(data))
    assert p.bind(vs).resolve() == data
    # pickled proxies stay tiny regardless of payload
    import pickle
    assert len(pickle.dumps(p)) < 200
