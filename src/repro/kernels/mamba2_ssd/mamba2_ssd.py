"""TPU Pallas kernel for the Mamba2 SSD chunked scan.

Grid: (B, H, num_chunks) with the chunk dimension minormost (sequential per
core); the inter-chunk state (P, N) is carried in f32 VMEM scratch, so HBM
sees each x/b/c chunk exactly once -- the scan's working set (a (Q,P) x
chunk, (Q,N) b/c chunks, the (P,N) state and the (Q,Q) decay matrix) fits
VMEM comfortably at the default Q=128, P=64, N<=256 (~0.5 MB f32).

Intra-chunk work is the quadratic "attention" form (two MXU matmuls); the
inter-chunk recurrence is a rank-Q state update, also a matmul.  Matches
ref.ssd_chunked numerics (same segsum formulation, unconditionally stable:
all exponents <= 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _segsum(log_a):
    """(Q,) -> (Q, Q) lower-tri pairwise sums: out[i,j]=sum_{j<s<=i} log_a[s]."""
    Q = log_a.shape[0]
    cs = jnp.cumsum(log_a)
    diff = cs[:, None] - cs[None, :]
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.where(i >= j, diff, -jnp.inf)


def _kernel(x_ref, la_ref, b_ref, c_ref, s0_ref, y_ref, sout_ref, s_scr,
            *, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0].astype(jnp.float32)        # (Q, P)
    la = la_ref[0, :, 0].astype(jnp.float32)      # (Q,)
    b = b_ref[0, :, 0].astype(jnp.float32)        # (Q, N)
    c = c_ref[0, :, 0].astype(jnp.float32)        # (Q, N)
    s = s_scr[...]                                # (P, N)

    # intra-chunk quadratic term
    Lmat = jnp.exp(_segsum(la))                   # (Q, Q), tri
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # (Q,Q)
    y_intra = jax.lax.dot((scores * Lmat).astype(x.dtype), x)     # (Q,P)

    # carry-in term
    cum = jnp.cumsum(la)                          # (Q,)
    decay_in = jnp.exp(cum)[:, None]              # (Q,1)
    y_inter = jax.lax.dot(c * decay_in,
                          s.transpose())          # (Q,N)@(N,P) -> (Q,P)

    y_ref[0, :, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S' = exp(total) S + sum_j decay_to_end[j] x_j b_j^T
    total = cum[-1]
    decay_to_end = jnp.exp(total - cum)[:, None]  # (Q,1)
    chunk_state = jax.lax.dot((x * decay_to_end).transpose(), b)  # (P,N)
    s_scr[...] = jnp.exp(total) * s + chunk_state

    @pl.when(ic == nc - 1)
    def _final():
        sout_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk",))
def ssd_pallas(x, log_a, b, c, initial_state=None, *, chunk: int = 128):
    """Same contract as ref.ssd_chunked. x (B,L,H,P); log_a (B,L,H);
    b/c (B,L,G,N); state (B,H,P,N)."""
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    group = (lambda h: h * G // H) if G != H else (lambda h: h)

    kernel = functools.partial(_kernel, nc=nc)
    y, s_out = pallas_call(
        kernel, x, log_a, b, c, initial_state,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, Q, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1, Q, 1, N),
                         lambda ib, ih, ic: (ib, ic, group(ih), 0)),
            pl.BlockSpec((1, Q, 1, N),
                         lambda ib, ih, ic: (ib, ic, group(ih), 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    return y, s_out
