"""Encoder-decoder transformer (seamless-m4t backbone).

The modality frontend is a stub per the assignment: the encoder consumes
precomputed frame embeddings (B, S_enc, D).  The decoder is a standard
causal stack with per-layer cross-attention to the encoder output; decode
carries {self-KV cache, precomputed cross-KV}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import rmsnorm, rmsnorm_params
from repro.models.transformer import (_ckpt, _scan_uniform, apply_dense_block,
                                      dense_block_params)


def encdec_stack_params(mk, cfg: ModelConfig):
    return {
        "encoder": dense_block_params(mk, cfg, stacked=(cfg.encoder_layers,)),
        "enc_norm": rmsnorm_params(mk, cfg.d_model),
        "decoder": dense_block_params(mk, cfg, stacked=(cfg.num_layers,),
                                      cross=True),
    }


def encode(params, frames, cfg: ModelConfig, *, cos, sin):
    """frames (B, S_enc, D) -> encoder output (B, S_enc, D)."""
    def app(p, x, c):
        del c
        h, _, aux = apply_dense_block(p, x, cfg, cos=cos, sin=sin,
                                      causal=False)
        return h, None, aux

    h, _, _ = _scan_uniform(params["encoder"], frames, cfg, app, None, False)
    return rmsnorm(params["enc_norm"], h, cfg.norm_eps)


def cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute per-decoder-layer cross K/V: leaves (L, B, S_enc, KVH, hd)."""
    def body(_, p):
        kv = attn.encode_cross_kv(p["cross"], enc_out, cfg)
        return None, kv

    _, kv = jax.lax.scan(body, None, params["decoder"])
    return kv


def run_decoder(params, h, cfg: ModelConfig, *, cos, sin, enc_kv,
                cache=None, cur_len=None, collect_cache=False):
    """Decoder stack with cross-attention. enc_kv leaves (L, B, S_enc, ...)."""
    def body(carry, xs):
        hh, aux = carry
        p, ekv, c = xs
        hh, new_c, a = apply_dense_block(
            p, hh, cfg, cos=cos, sin=sin, cache=c, cur_len=cur_len,
            enc_kv=ekv, collect_cache=collect_cache)
        return (hh, aux + a), new_c

    body = _ckpt(body, cfg)
    (h, aux), new_cache = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)),
        (params["decoder"], enc_kv, cache), length=cfg.num_layers)
    return h, new_cache, aux
