"""Pure-jnp oracles for the Mamba2 state-space-dual (SSD) scan.

Contract (shared by ref, naive and Pallas implementations):

    y, final_state = ssd(x, log_a, b, c, initial_state, chunk)

    x:      (B, L, H, P)   inputs, already scaled by dt
    log_a:  (B, L, H)      per-step log decay, log a_t <= 0
    b:      (B, L, G, N)   input projections  (G groups; H % G == 0)
    c:      (B, L, G, N)   output projections
    state:  (B, H, P, N)

    recurrence (per head h with group g = h * G // H):
        S_t = a_t * S_{t-1} + x_t (outer) b_t
        y_t = S_t @ c_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _expand_groups(t, H):
    """(B, L, G, N) -> (B, L, H, N) by repeating each group."""
    B, L, G, N = t.shape
    rep = H // G
    return jnp.repeat(t, rep, axis=2) if rep > 1 else t


def ssd_naive(x, log_a, b, c, initial_state=None):
    """Step-by-step scan; the ground-truth oracle for tests."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    bf = _expand_groups(b.astype(jnp.float32), H)
    cf = _expand_groups(c.astype(jnp.float32), H)
    xf = x.astype(jnp.float32)
    af = jnp.exp(log_a.astype(jnp.float32))
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(s, inp):
        xt, at, bt, ct = inp          # (B,H,P), (B,H), (B,H,N), (B,H,N)
        s = s * at[..., None, None] + xt[..., None] * bt[..., None, :]
        yt = jnp.einsum("bhpn,bhn->bhp", s, ct)
        return s, yt

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(af, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    s, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)          # (B,L,H,P)
    return y, s


def _segsum(log_a):
    """(..., Q) -> (..., Q, Q) lower-triangular pairwise decay sums:
    out[i, j] = sum_{j < s <= i} log_a[s]  (i >= j), -inf above diagonal."""
    Q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # cum[i] - cum[j]
    i = jnp.arange(Q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, log_a, b, c, initial_state=None, chunk: int = 128):
    """Chunked SSD: quadratic intra-chunk attention + inter-chunk recurrence.

    Identical numerics target as ssd_naive; O(L/Q) sequential steps.
    """
    B, L, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    xf = x.astype(jnp.float32).reshape(B, nc, Q, H, P)
    la = log_a.astype(jnp.float32).reshape(B, nc, Q, H)
    bf = _expand_groups(b.astype(jnp.float32), H).reshape(B, nc, Q, H, N)
    cf = _expand_groups(c.astype(jnp.float32), H).reshape(B, nc, Q, H, N)
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    # intra-chunk ("attention") term, computed in parallel over chunks
    la_t = jnp.moveaxis(la, -1, 2)                      # (B,nc,H,Q)
    Lmat = jnp.exp(_segsum(la_t))                       # (B,nc,H,Q,Q)
    scores = jnp.einsum("bnihs,bnjhs->bnhij", cf, bf)   # (B,nc,H,Q,Q)
    y_intra = jnp.einsum("bnhij,bnhij,bnjhp->bnihp",
                         scores, Lmat, xf)              # (B,nc,Q,H,P)

    # per-chunk aggregated state contribution and total decay
    cum = jnp.cumsum(la_t, axis=-1)                     # (B,nc,H,Q)
    total = cum[..., -1:]                               # (B,nc,H,1)
    decay_to_end = jnp.exp(total - cum)                 # (B,nc,H,Q)
    chunk_state = jnp.einsum("bnjhs,bnhj,bnjhp->bnhps",
                             bf, decay_to_end, xf)      # (B,nc,H,P,N)

    # inter-chunk recurrence over nc steps
    def step(s, inp):
        cs, tot = inp                                   # (B,H,P,N), (B,H,1)
        s_in = s
        s = s * jnp.exp(tot)[..., None] + cs
        return s, s_in

    xs = (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(total, 1, 0))
    s_final, s_prevs = jax.lax.scan(step, s0, xs)
    s_prev = jnp.moveaxis(s_prevs, 0, 1)                # (B,nc,H,P,N)

    # inter-chunk output: y_t += C_t . (decay_in(t) * S_prev)
    decay_in = jnp.exp(cum)                             # (B,nc,H,Q)
    y_inter = jnp.einsum("bnihs,bnhi,bnhps->bnihp", cf, decay_in, s_prev)

    y = (y_intra + y_inter).reshape(B, L, H, P).astype(x.dtype)
    return y, s_final


def ssd_step(x_t, log_a_t, b_t, c_t, state):
    """Single decode step. x_t (B,H,P); log_a_t (B,H); b/c (B,G,N);
    state (B,H,P,N) -> (y (B,H,P), new_state)."""
    H = x_t.shape[1]
    bf = _expand_groups(b_t[:, None].astype(jnp.float32), H)[:, 0]
    cf = _expand_groups(c_t[:, None].astype(jnp.float32), H)[:, 0]
    a = jnp.exp(log_a_t.astype(jnp.float32))
    s = state.astype(jnp.float32) * a[..., None, None] \
        + x_t.astype(jnp.float32)[..., None] * bf[..., None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, cf)
    return y.astype(x_t.dtype), s
