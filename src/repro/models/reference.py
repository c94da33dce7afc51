"""Plain float32 reference forward for dense decoder configs.

Written from the architecture, not from the model code: no scan
helpers, cache, chunking, sharding constraints or kernels.  Weights are
cast to float32 one layer at a time, so the reference needs one extra
float32 copy of a layer, the embedding and the unembedding beside the
model's own (bf16) weights.  Run it under
``jax.default_matmul_precision("highest")``: otherwise the TPU does
float32 matmuls in bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _check_supported(cfg: ModelConfig) -> None:
    plain = (cfg.family == "dense" and not cfg.is_moe and not cfg.is_encdec
             and not cfg.local_global_period and cfg.sliding_window is None
             and not cfg.qk_norm and not cfg.post_norm and not cfg.emb_scale
             and cfg.attn_logit_softcap is None
             and cfg.final_logit_softcap is None
             and cfg.mrope_sections is None and not cfg.tie_embeddings)
    if not plain:
        raise ValueError(f"{cfg.name}: the reference covers plain dense"
                         " decoders only")


def last_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """tokens (B, S) -> float32 logits (B, V) at the last position."""
    _check_supported(cfg)
    f32 = jnp.float32
    S = tokens.shape[1]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.act]

    def norm(x, w):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + cfg.norm_eps) * w.astype(f32))

    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                                              # (B,S,h,hd)
        t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        p = jax.tree.map(lambda t: t.astype(f32), p)
        a, at = norm(x, p["ln1"]["scale"]), p["attn"]
        q = rope(jnp.einsum("bsd,dhk->bshk", a, at["wq"]))
        k = rope(jnp.einsum("bsd,dhk->bshk", a, at["wk"]))
        v = jnp.einsum("bsd,dhk->bshk", a, at["wv"])
        # query head h reads kv head h // (H // KV)
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(f32(hd))
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", w, v)
        x = x + jnp.einsum("bqhk,hkd->bqd", o, at["wo"])
        m, ff = norm(x, p["ln2"]["scale"]), p["ffn"]
        x = x + (act(m @ ff["wi_gate"]) * (m @ ff["wi_up"])) @ ff["wo"]
        return x, None

    x = params["tok"]["embed"].astype(f32)[tokens]
    x, _ = jax.lax.scan(layer, x, params["stack"]["uniform"])
    x = norm(x[:, -1], params["final_norm"]["scale"])
    return x @ params["tok"]["unembed"].astype(f32)
