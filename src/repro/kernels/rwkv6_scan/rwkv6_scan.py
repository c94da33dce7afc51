"""TPU Pallas kernel for the RWKV6 WKV recurrence.

Grid: (B, H, num_chunks), chunk dimension minormost; the (K, V) state is
carried in f32 VMEM scratch.  The carry-in contribution for a whole chunk
is one MXU matmul, (r * decay_in)(Q,K) @ S(K,V); the intra-chunk term uses
the sequential per-step loop (numerically exact for arbitrary
data-dependent decay -- the fully-parallel form overflows f32, see
ref.wkv6_chunked).  The loop body is rank-1 work; Q=64 keeps the sequential
fraction small while the (Q,K)x(K,V) matmuls feed the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, *, nc: int, Q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)        # (Q, K)
    k = k_ref[0, :, 0].astype(jnp.float32)        # (Q, K)
    v = v_ref[0, :, 0].astype(jnp.float32)        # (Q, V)
    lw = lw_ref[0, :, 0].astype(jnp.float32)      # (Q, K)
    u = u_ref[0].astype(jnp.float32)              # (K,)
    s_in = s_scr[...]                             # (K, V)

    # carry-in term for every step of the chunk: one MXU matmul
    cum = jnp.cumsum(lw, axis=0)                  # (Q, K)
    decay_in = jnp.exp(cum - lw)                  # prod_{s<=t-1} w, <= 1
    y_inter = jax.lax.dot(r * decay_in, s_in)     # (Q, V)

    # intra-chunk: exact sequential recurrence from zero state
    def step(t, carry):
        s, y = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)     # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)     # (1, V)
        wt = jnp.exp(jax.lax.dynamic_slice_in_dim(lw, t, 1, 0))
        kv = kt.transpose() * vt                          # (K, V)
        yt = jax.lax.dot(rt, s + u[:, None] * kv)         # (1, V)
        y = jax.lax.dynamic_update_slice_in_dim(y, yt, t, 0)
        s = s * wt.transpose() + kv
        return s, y

    s_c, y_intra = jax.lax.fori_loop(
        0, Q, step, (jnp.zeros_like(s_in), jnp.zeros((Q, v.shape[1]),
                                                     jnp.float32)))
    y_ref[0, :, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    total = cum[-1]                               # (K,)
    s_scr[...] = jnp.exp(total)[:, None] * s_in + s_c

    @pl.when(ic == nc - 1)
    def _final():
        sout_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_pallas(r, k, v, log_w, u, initial_state=None, *, chunk: int = 64):
    """Same contract as ref.wkv6_chunked. r/k/log_w (B,L,H,K); v (B,L,H,V);
    u (H,K); state (B,H,K,V)."""
    B, L, H, K = r.shape
    V = v.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), jnp.float32)

    kernel = functools.partial(_kernel, nc=nc, Q=Q)
    y, s_out = pallas_call(
        kernel, r, k, v, log_w, u, initial_state,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, 1, K), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, Q, 1, K), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, Q, 1, V), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, Q, 1, K), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, K), lambda ib, ih, ic: (ih, 0)),
            pl.BlockSpec((1, 1, K, V), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, V), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, K, V), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
    )
    return y, s_out
