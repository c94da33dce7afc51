"""Attention: GQA projections + chunked flash-style reference math.

The reference implementation (`mha_reference`) is a blockwise online-softmax
attention in pure jnp: it never materializes the full (Sq, Sk) score matrix,
skips fully-masked KV chunks at *trace* time (so sliding-window layers cost
only their window), and supports:

- grouped-query attention (num_kv_heads < num_heads),
- causal masking with a query position offset (decode),
- static sliding windows (gemma2 local layers),
- attention-logit softcapping (gemma2),
- dynamic valid-length masking (decode against a partially filled cache).

The TPU Pallas kernel (`repro.kernels.flash_attention`) implements the same
contract; `attend` runs the reference until a kernel wins a measured cell.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.distributed import axisenv
from repro.models.layers import apply_rope, rmsnorm_head

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attention_params(mk, cfg: ModelConfig, stacked=(), cross: bool = False):
    """Projection weights for one attention module (self or cross)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    lead = tuple("layer" for _ in stacked)
    p = {
        "wq": mk.param(stacked + (d, nh, hd), lead + ("embed", "heads", "head_dim"),
                       fan_in=d),
        "wk": mk.param(stacked + (d, nkv, hd), lead + ("embed", "kv_heads", "head_dim"),
                       fan_in=d),
        "wv": mk.param(stacked + (d, nkv, hd), lead + ("embed", "kv_heads", "head_dim"),
                       fan_in=d),
        "wo": mk.param(stacked + (nh, hd, d), lead + ("heads", "head_dim", "embed"),
                       fan_in=nh * hd),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = mk.param(stacked + (hd,), lead + ("head_dim",), init="ones")
        p["k_norm"] = mk.param(stacked + (hd,), lead + ("head_dim",), init="ones")
    return p


# ---------------------------------------------------------------------------
# Core math: blockwise online-softmax attention
# ---------------------------------------------------------------------------


def _chunk_alive(causal: bool, window: Optional[int],
                 q0: int, q1: int, k0: int, k1: int) -> bool:
    """Static reachability of a (q-chunk, kv-chunk) pair. Positions are
    absolute (q already offset). q/k ranges are [q0, q1), [k0, k1)."""
    if causal and k0 > q1 - 1:
        return False            # chunk entirely in the future
    if window is not None and q0 - (k1 - 1) >= window:
        return False            # chunk entirely beyond the look-back window
    return True


def mha_reference(
    q: jax.Array,              # (B, Sq, H, hd)
    k: jax.Array,              # (B, Sk, KVH, hd)
    v: jax.Array,              # (B, Sk, KVH, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,       # static look-back window (None = full)
    softcap: Optional[float] = None,
    q_offset=0,                         # static int OR scalar array (decode)
    valid_len=None,                     # scalar array: kv positions < valid are real
    chunk_q: int = 1024,
    chunk_k: int = 1024,
) -> jax.Array:
    """Blockwise attention; returns (B, Sq, H, hd) in q.dtype.

    GQA in the blockwise path *repeats* K/V to the full head count instead
    of reshaping Q to (KVH, G, hd): the repeat keeps the head axis intact,
    so a "model"-sharded head dimension propagates through every einsum
    with no resharding (the (KVH, G) reshape misaligns GSPMD shard
    boundaries whenever KVH < the mesh axis).  The repeat is a chunk-local
    transient.  The decode path (Sq <= 8) reads the whole cache per call,
    so there it views q as (KVH, G) and reads K/V as stored, unless a mesh
    axis shards the heads (see `_decode_attention`).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    assert H % KVH == 0, (H, KVH)
    G = H // KVH
    scale = hd ** -0.5
    dyn_offset = not isinstance(q_offset, int)
    # For static chunk skipping when the offset is dynamic (decode), the only
    # safe static bound is "q is somewhere in [0, inf)" -> no skipping unless
    # windowed; decode Sq is tiny so this costs nothing.
    static_q0 = 0 if dyn_offset else q_offset

    qf = q.astype(jnp.float32) * scale
    kpos_all = jnp.arange(Sk)

    # decode fast path: tiny Sq, single pass over the whole cache
    if Sq <= 8:
        qpos = jnp.arange(Sq) + q_offset
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= kpos_all[None, :] <= qpos[:, None]
        if window is not None:
            mask &= qpos[:, None] - kpos_all[None, :] < window
        if valid_len is not None:
            mask &= kpos_all[None, :] < valid_len
        o = _decode_attention(qf, k, v, mask, softcap)
        return o.astype(q.dtype)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def kv_chunk(t, k0, k1):
        c = t[:, k0:k1]
        return jnp.repeat(c, G, axis=2) if G > 1 else c   # (B,ck,H,hd)

    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    n_q, n_k = -(-Sq // cq), -(-Sk // ck)
    assert Sq % cq == 0 and Sk % ck == 0, "seq must divide chunk sizes"

    out_chunks = []
    for iq in range(n_q):
        q0s = static_q0 + iq * cq                    # static lower bound
        qc = qf[:, iq * cq:(iq + 1) * cq]            # (B,cq,H,hd)
        qpos = jnp.arange(iq * cq, (iq + 1) * cq) + q_offset  # (cq,) abs
        m = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, cq), jnp.float32)
        acc = jnp.zeros((B, cq, H, hd), jnp.float32)
        for ik in range(n_k):
            k0, k1 = ik * ck, (ik + 1) * ck
            if not dyn_offset and not _chunk_alive(
                    causal, window, q0s, q0s + cq, k0, k1):
                continue
            kc, vc = kv_chunk(kf, k0, k1), kv_chunk(vf, k0, k1)
            s = jnp.einsum("bihd,bjhd->bhij", qc, kc)    # (B,H,cq,ck)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = kpos_all[k0:k1]
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            if valid_len is not None:
                mask &= kpos[None, :] < valid_len
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = (acc * jnp.transpose(corr, (0, 2, 1))[..., None]
                   + jnp.einsum("bhij,bjhd->bihd", p, vc))
            m = m_new
        l_t = jnp.transpose(l, (0, 2, 1))                 # (B,cq,H)
        out_chunks.append(acc / jnp.maximum(l_t, 1e-30)[..., None])
    o = jnp.concatenate(out_chunks, axis=1) if n_q > 1 else out_chunks[0]
    return o.astype(q.dtype)                              # (B,Sq,H,hd)


def _decode_attention(qf, k, v, mask, softcap):
    """One pass of f32 attention over the whole cache for a few queries.

    qf (B, Sq, H, hd) f32, pre-scaled; k/v (B, Sk, KVH, hd) in the cache's
    dtype; mask (Sq, Sk).  Scores, softmax and the P.V sum are f32; K/V are
    upcast inside the reductions, which is exact.  With the heads
    unsharded, GQA views q as (B, Sq, KVH, G, hd) against K/V as stored:
    each layer's cache is read once, with no f32 or group-repeated copy.
    Where a mesh axis shards the heads, K/V are repeated to H heads, which
    keeps the head axis whole for GSPMD (see `mha_reference`).
    """
    B, Sq, H, hd = qf.shape
    G = H // k.shape[2]
    if axisenv.resolve("model", H) is None:
        qf = qf.reshape(B, Sq, k.shape[2], G, hd)
        eq_s, eq_o = "bikgd,bjkd->bkgij", "bkgij,bjkd->bikgd"
    else:
        if G > 1:
            k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        eq_s, eq_o = "bihd,bjhd->bhij", "bhij,bjhd->bihd"
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum(eq_s, qf, k.astype(jnp.float32), precision=hi)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    o = jnp.einsum(eq_o, p, v.astype(jnp.float32), precision=hi)
    return o.reshape(B, Sq, H, hd)


def attend(q, k, v, *, cfg: ModelConfig, causal=True, window=None,
           q_offset=0, valid_len=None):
    """Attention with the model's softcap and chunk size."""
    return mha_reference(
        q, k, v, causal=causal, window=window,
        softcap=cfg.attn_logit_softcap, q_offset=q_offset,
        valid_len=valid_len, chunk_q=cfg.attn_chunk, chunk_k=cfg.attn_chunk)


# ---------------------------------------------------------------------------
# Full attention block step (projections + rope + cache + attention)
# ---------------------------------------------------------------------------


def _project_kv(params, x, cfg: ModelConfig):
    """Fused K/V input matmul: one gather of x feeds both projections.
    Fused along a new leading axis (wk/wv have identical shapes), NOT
    concatenated along heads: the kv-head axis of both halves stays
    aligned with its "kv" shards, so the k/v split is always shard-local.
    Fusing Q in as well would require a concat across the *differing* head
    counts (H vs KVH under GQA) -- a concat+split across a sharded dim,
    which GSPMD has miscompiled -- so Q stays separate.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.fuse_kv:
        wkv = jnp.stack([params["wk"], params["wv"]]).astype(cd)
        kv = jnp.einsum("bsd,gdhk->gbshk", x, wkv)
        return kv[0], kv[1]
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cd))
    return k, v


def project_qkv(params, x, cfg: ModelConfig, cos=None, sin=None):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,KVH,hd); applies qk-norm + rope."""
    cd = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cd))
    k, v = _project_kv(params, x, cfg)
    q = axisenv.constrain(q, "batch", None, "model", None)
    k = axisenv.constrain(k, "batch", None, "kv", None)
    v = axisenv.constrain(v, "batch", None, "kv", None)
    if "q_norm" in params:
        q = rmsnorm_head(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_head(params["k_norm"], k, cfg.norm_eps)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def output_proj(params, o, cfg: ModelConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    o = axisenv.constrain(o, "batch", None, "model", None)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(cd))
    return axisenv.constrain(out, "batch", None, None)


def self_attention(params, x, cfg: ModelConfig, *, cos, sin, causal=True,
                   window=None, cache=None, cur_len=None, layer=None):
    """One self-attention application.

    cache: None (full-sequence) or dict {k, v} of (B, S_max, KVH, hd) arrays;
    with `layer` (a scalar array), the layer-stacked (L, B, S_max, KVH, hd)
    cache, of which only layer `layer` is written and read.
    cur_len: scalar array; when cache is given, the new tokens are written at
    [cur_len, cur_len + Sq) and attention sees positions < cur_len + Sq.
    Returns (out (B,Sq,D), new_cache).
    """
    q, k_new, v_new = project_qkv(params, x, cfg, cos, sin)
    if cache is None:
        o = attend(q, k_new, v_new, cfg=cfg, causal=causal, window=window)
        return output_proj(params, o, cfg), None
    # decode / incremental path
    def heads(t):
        return axisenv.constrain(t, "batch", None, "kv", None)

    at = (0, _as_idx(cur_len), 0, 0)
    if layer is None:
        k = heads(jax.lax.dynamic_update_slice(cache["k"], k_new, at))
        v = heads(jax.lax.dynamic_update_slice(cache["v"], v_new, at))
        cache = {"k": k, "v": v}
    else:
        # only the new positions are written, into the stacked cache in
        # place; attention reads the layer's slice of the stack
        at = (_as_idx(layer),) + at
        cache = {n: _stacked_in_place(
                     jax.lax.dynamic_update_slice(cache[n], t[None], at))
                 for n, t in (("k", k_new), ("v", v_new))}
        k, v = (heads(jax.lax.dynamic_index_in_dim(cache[n], at[0],
                                                   keepdims=False))
                for n in ("k", "v"))
    valid = cur_len + x.shape[1]
    o = attend(q, k, v, cfg=cfg, causal=True, window=window,
               q_offset=cur_len, valid_len=valid)
    return output_proj(params, o, cfg), cache


def _stacked_in_place(t):
    """A layer-stacked cache inside the decode loop, pinned to the sharding
    and the row-major layout it has outside: left free, XLA lays the loop's
    copy out for the attention reads, so every step would relayout the
    whole cache on entry and back on exit."""
    t = axisenv.constrain(t, None, "batch", None, "kv", None)
    return with_layout_constraint(t, Layout(tuple(range(t.ndim))))


def cross_attention(params, x, enc_kv, cfg: ModelConfig):
    """Decoder cross-attention; enc_kv = {k, v} precomputed from encoder."""
    cd = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cd))
    o = attend(q, enc_kv["k"], enc_kv["v"], cfg=cfg, causal=False)
    return output_proj(params, o, cfg)


def encode_cross_kv(params, enc_out, cfg: ModelConfig):
    k, v = _project_kv(params, enc_out, cfg)
    return {"k": k, "v": v}


def _as_idx(i):
    return i if isinstance(i, int) else i.astype(jnp.int32)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, layers: int,
                  dtype=None):
    """Stacked-over-layers KV cache pytree."""
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    shape = (layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
