"""Public model API: one set of entry points for every assigned arch.

    params  = init_params(cfg, key)            # concrete jnp arrays
    specs   = param_specs(cfg)                 # logical-axis tuples (same tree)
    logits, aux = forward(params, cfg, batch)  # train / full-sequence
    loss, metrics = loss_fn(params, cfg, batch)
    logits, cache = prefill(params, cfg, batch)
    logits, cache = decode_step(params, cfg, cache, tokens, cur_len)

batch keys: "tokens" (B,S) int32 OR "embeds" (B,S,D) for stub-frontend archs
(vlm/audio); "labels" (B,S); "positions" optional ((3,B,S) for M-RoPE);
enc-dec additionally takes "frames" (B,S_enc,D) for the encoder.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import (InitMaker, SpecMaker, embed,
                                 embedding_params, rmsnorm, rmsnorm_params,
                                 softmax_cross_entropy, unembed)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def model_params(mk, cfg: ModelConfig):
    p = {
        "tok": embedding_params(mk, cfg),
        "final_norm": rmsnorm_params(mk, cfg.d_model),
    }
    if cfg.is_encdec:
        p["stack"] = encdec.encdec_stack_params(mk, cfg)
    else:
        p["stack"] = transformer.stack_params(mk, cfg)
    return p


def init_params(cfg: ModelConfig, key):
    mk = InitMaker(key, jnp.dtype(cfg.param_dtype))
    return model_params(mk, cfg)


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct tree without allocating (for ahead-of-time
    lowering)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_specs(cfg: ModelConfig):
    return model_params(SpecMaker(), cfg)


# ---------------------------------------------------------------------------
# Embedding front
# ---------------------------------------------------------------------------


def _embed_input(params, cfg, batch):
    if batch.get("embeds") is not None:
        h = batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
    else:
        h = embed(params["tok"], batch["tokens"], cfg)
    B, S = h.shape[0], h.shape[1]
    pos = batch.get("positions")
    if pos is None:
        pos = transformer.positions_for(cfg, B, S)
    cos, sin = transformer.rope_tables(cfg, pos)
    return h, cos, sin


# ---------------------------------------------------------------------------
# Full-sequence forward (training) and loss
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, batch):
    h, cos, sin = _embed_input(params, cfg, batch)
    if cfg.is_encdec:
        frames = batch["frames"].astype(h.dtype)
        epos = transformer.positions_for(cfg, frames.shape[0], frames.shape[1])
        ecos, esin = transformer.rope_tables(cfg, epos)
        enc_out = encdec.encode(params["stack"], frames, cfg,
                                cos=ecos, sin=esin)
        ekv = encdec.cross_kv(params["stack"], enc_out, cfg)
        h, _, aux = encdec.run_decoder(params["stack"], h, cfg, cos=cos,
                                       sin=sin, enc_kv=ekv)
    else:
        h, _, aux = transformer.run_stack(params["stack"], h, cfg,
                                          cos=cos, sin=sin)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params["tok"], h, cfg)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux = forward(params, cfg, batch)
    ce, count = softmax_cross_entropy(logits, batch["labels"],
                                      batch.get("loss_mask"))
    loss = ce
    if cfg.is_moe:
        loss = loss + cfg.router_aux_coef * aux
    metrics = {"ce": ce, "aux": aux, "tokens": count}
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, batch):
    """Full-sequence forward that also materializes the decode cache.
    Returns (last-position logits (B,V), cache)."""
    h, cos, sin = _embed_input(params, cfg, batch)
    if cfg.is_encdec:
        frames = batch["frames"].astype(h.dtype)
        epos = transformer.positions_for(cfg, frames.shape[0], frames.shape[1])
        ecos, esin = transformer.rope_tables(cfg, epos)
        enc_out = encdec.encode(params["stack"], frames, cfg,
                                cos=ecos, sin=esin)
        ekv = encdec.cross_kv(params["stack"], enc_out, cfg)
        h, self_kv, _ = encdec.run_decoder(params["stack"], h, cfg, cos=cos,
                                           sin=sin, enc_kv=ekv,
                                           collect_cache=True)
        cache = {"self": self_kv, "cross": ekv}
    else:
        h, cache, _ = transformer.run_stack(params["stack"], h, cfg, cos=cos,
                                            sin=sin, collect_cache=True)
    h = rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    logits = unembed(params["tok"], h, cfg)
    return logits[:, 0], cache


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_len):
    """One decode step. tokens (B,1); cur_len scalar int array: number of
    positions already in the cache. Returns (logits (B,V), new_cache)."""
    batch = {"tokens": tokens}
    B = tokens.shape[0]
    pos = transformer.positions_for(cfg, B, 1, offset=cur_len)
    h = embed(params["tok"], tokens, cfg)
    cos, sin = transformer.rope_tables(cfg, pos)
    if cfg.is_encdec:
        h, self_kv, _ = encdec.run_decoder(
            params["stack"], h, cfg, cos=cos, sin=sin,
            enc_kv=cache["cross"], cache=cache["self"], cur_len=cur_len)
        new_cache = {"self": self_kv, "cross": cache["cross"]}
    else:
        h, new_cache, _ = transformer.run_stack(
            params["stack"], h, cfg, cos=cos, sin=sin, cache=cache,
            cur_len=cur_len)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params["tok"], h, cfg)
    return logits[:, 0], new_cache


def grow_cache(cfg: ModelConfig, cache, new_capacity: int):
    """Pad attention KV caches along the sequence axis to `new_capacity`
    (SSM/conv/shift states are length-independent and pass through)."""
    def pad_kv(kv):
        def pad(t):
            cap = t.shape[2]
            if cap >= new_capacity:
                return t
            widths = [(0, 0)] * t.ndim
            widths[2] = (0, new_capacity - cap)
            return jnp.pad(t, widths)
        return {"k": pad(kv["k"]), "v": pad(kv["v"])}

    if cfg.is_encdec:
        return {"self": pad_kv(cache["self"]), "cross": cache["cross"]}
    if cfg.rwkv:
        return cache
    if cfg.family == "hybrid":
        return {"mamba": cache["mamba"], "attn": pad_kv(cache["attn"])}
    return pad_kv(cache)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0):
    if cfg.is_encdec:
        hd = cfg.resolved_head_dim
        dt = jnp.dtype(cfg.compute_dtype)
        cross = {
            "k": jnp.zeros((cfg.num_layers, batch, enc_len,
                            cfg.num_kv_heads, hd), dt),
            "v": jnp.zeros((cfg.num_layers, batch, enc_len,
                            cfg.num_kv_heads, hd), dt),
        }
        return {"self": transformer.init_cache(cfg, batch, max_len),
                "cross": cross}
    return transformer.init_cache(cfg, batch, max_len)
