"""Pure-jnp oracles for the RWKV6 ("Finch") WKV recurrence.

Contract (shared by ref, naive and Pallas implementations):

    y, final_state = wkv6(r, k, v, log_w, u, initial_state, chunk)

    r:      (B, L, H, K)   receptance
    k:      (B, L, H, K)   key
    v:      (B, L, H, V)   value
    log_w:  (B, L, H, K)   per-step, per-channel log decay (data-dependent!)
    u:      (H, K)         "bonus" for the current token
    state:  (B, H, K, V)

    recurrence:
        y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wkv6_naive(r, k, v, log_w, u, initial_state=None):
    """Step-by-step scan; ground-truth oracle for tests."""
    B, L, H, K = r.shape
    V = v.shape[-1]
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    wf = jnp.exp(log_w.astype(jnp.float32))
    uf = u.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(s, inp):
        rt, kt, vt, wt = inp     # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = kt[..., None] * vt[..., None, :]             # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + uf[..., None] * kv)
        s = s * wt[..., None] + kv
        return s, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    s, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), s


def wkv6_chunked(r, k, v, log_w, u, initial_state=None, chunk: int = 64):
    """Chunked WKV6: sequential scan *within* each chunk (vectorized across
    all chunks, so the sequential depth is Q + L/Q instead of L) plus an
    analytic inter-chunk recurrence.

    The fully-parallel intra-chunk form needs exp(+|cumsum log w|) factors
    that overflow f32 for strong data-dependent decay; this hybrid is exact
    and unconditionally stable, and is also the blocked structure the Pallas
    kernel uses.
    """
    B, L, H, K = r.shape
    V = v.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    rf = r.astype(jnp.float32).reshape(B * nc, Q, H, K)
    kf = k.astype(jnp.float32).reshape(B * nc, Q, H, K)
    vf = v.astype(jnp.float32).reshape(B * nc, Q, H, V)
    lw = log_w.astype(jnp.float32).reshape(B * nc, Q, H, K)
    uf = u.astype(jnp.float32)
    s0 = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    # intra-chunk term from zero state, all chunks at once
    y_intra, chunk_state = wkv6_naive(rf, kf, vf, lw, uf)
    y_intra = y_intra.reshape(B, nc, Q, H, V).astype(jnp.float32)
    chunk_state = chunk_state.reshape(B, nc, H, K, V)

    cum = jnp.cumsum(lw.reshape(B, nc, Q, H, K), axis=2)    # log prod_{s<=t}
    total = cum[:, :, -1]                                   # (B,nc,H,K)
    decay_in = jnp.exp(cum - lw.reshape(B, nc, Q, H, K))    # prod_{s<=t-1} <=1

    # inter-chunk recurrence over nc steps
    def step(s, inp):
        cs, tot = inp
        s_in = s
        s = s * jnp.exp(tot)[..., None] + cs
        return s, s_in

    xs = (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(total, 1, 0))
    s_final, s_prevs = jax.lax.scan(step, s0, xs)
    s_prev = jnp.moveaxis(s_prevs, 0, 1)                    # (B,nc,H,K,V)

    # carry-in contribution: r_t . diag(prod_{s<=t-1} w) S_prev
    rr = rf.reshape(B, nc, Q, H, K)
    y_inter = jnp.einsum("bnihk,bnihk,bnhkv->bnihv", rr, decay_in, s_prev)

    y = (y_inter + y_intra).reshape(B, L, H, V).astype(r.dtype)
    return y, s_final


def wkv6_step(r_t, k_t, v_t, log_w_t, u, state):
    """Single decode step. r/k/log_w (B,H,K), v (B,H,V), state (B,H,K,V)."""
    rf, kf, vf = (t.astype(jnp.float32) for t in (r_t, k_t, v_t))
    wf = jnp.exp(log_w_t.astype(jnp.float32))
    s = state.astype(jnp.float32)
    kv = kf[..., None] * vf[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", rf, s + u.astype(jnp.float32)[..., None] * kv)
    s = s * wf[..., None] + kv
    return y.astype(r_t.dtype), s
