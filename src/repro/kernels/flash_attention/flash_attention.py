"""TPU Pallas flash attention (blockwise online softmax).

Grid: (batch*q_heads, num_q_blocks, num_kv_blocks) -- the KV dimension is
minormost so each (bh, iq) pair iterates its KV blocks sequentially on a
TPU core while the online-softmax state (m, l, acc) lives in VMEM scratch.
GQA is handled in the k/v index maps (query head bh reads KV head bh // G),
so K/V are never physically repeated.  Causal masking, static sliding
windows and logit softcap are supported; fully-masked KV blocks are skipped
with pl.when (they still occupy grid slots -- the q-block-aligned variant
that trims them is a perf lever, not a semantics change).

Block shapes default to (128, head_dim) tiles: MXU-aligned on the matmul
dims and small enough that q/k/v blocks + f32 scratch fit VMEM
(3*128*hd*2B + 128*hd*4B + 128*128*4B ~ 360 KB at hd=128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], q_offset: int, bq: int, bk: int,
            nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos0 = q_offset + iq * bq
    kpos0 = ik * bk
    # static-shape live test for this (iq, ik) pair:
    live = True
    if causal:
        live = jnp.asarray(kpos0 <= qpos0 + bq - 1)
    if window is not None:
        live = jnp.logical_and(
            live, qpos0 - (kpos0 + bk - 1) < window) if causal else \
            jnp.asarray(qpos0 - (kpos0 + bk - 1) < window)

    @pl.when(live if not isinstance(live, bool) else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)[:, None]              # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_scr[...] = l_prev * corr + jnp.sum(p, -1)[:, None]
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot(p.astype(v.dtype), v))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset",
                     "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q (B,Sq,H,hd); k/v (B,Sk,KVH,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    # (B,S,H,hd) -> (B*H, S, hd) rows; kv rows indexed by bh // G
    qr = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(B * KVH, Sk, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(B * KVH, Sk, hd)

    def kv_row(bh):
        return (bh // (H // KVH)) if G > 1 else bh

    kernel = functools.partial(
        _kernel, scale=hd ** -0.5, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, bq=bq, bk=bk, nk=nk)

    out = pallas_call(
        kernel, qr, kr, vr,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_row(bh), ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_row(bh), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
    )
    return jnp.moveaxis(out.reshape(B, H, Sq, hd), 1, 2)
