"""RWKV6 "Finch" block: token-shift time mix with data-dependent decay
(WKV recurrence in repro.kernels.rwkv6_scan) + channel mix FFN.

Simplifications vs. the released RWKV6: the five
token-shift mixing coefficients are static learned vectors (the low-rank
data-dependent ddlerp is applied only to the decay, which is the part that
changes the recurrence class); the decay LoRA has rank cfg.rwkv_lora.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.rwkv6_scan import ops as wkv_ops
from repro.models.layers import rmsnorm

MIN_LOG_W = -12.0
RWKV_LORA = 64


def rwkv_dims(cfg: ModelConfig):
    K = cfg.rwkv_head_size
    H = cfg.d_model // K
    return H, K


def rwkv_time_mix_params(mk, cfg: ModelConfig, stacked=()):
    d = cfg.d_model
    H, K = rwkv_dims(cfg)
    lead = tuple("layer" for _ in stacked)
    p = {}
    for name in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w"):
        p[name] = mk.param(stacked + (d,), lead + ("embed",), init="zeros")
    for name in ("wr", "wk", "wv", "wg", "wo"):
        p[name] = mk.param(stacked + (d, d), lead + ("embed", "embed2"),
                           fan_in=d)
    p["w0"] = mk.param(stacked + (d,), lead + ("embed",), init="zeros")
    p["w_lora_a"] = mk.param(stacked + (d, RWKV_LORA),
                             lead + ("embed", "lora"), fan_in=d)
    p["w_lora_b"] = mk.param(stacked + (RWKV_LORA, d),
                             lead + ("lora", "embed"), scale=0.01)
    p["u"] = mk.param(stacked + (H, K), lead + ("heads", "head_dim"),
                      init="zeros")
    p["ln_x"] = mk.param(stacked + (d,), lead + ("embed",), init="ones")
    return p


def rwkv_channel_mix_params(mk, cfg: ModelConfig, stacked=()):
    d, f = cfg.d_model, cfg.d_ff
    lead = tuple("layer" for _ in stacked)
    return {
        "mu_k": mk.param(stacked + (d,), lead + ("embed",), init="zeros"),
        "wk": mk.param(stacked + (d, f), lead + ("embed", "ff"), fan_in=d),
        "wv": mk.param(stacked + (f, d), lead + ("ff", "embed"), fan_in=f),
    }


def _token_shift(x, prev):
    """shifted[t] = x[t-1]; position 0 takes `prev` (B,1,D) or zeros."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _mix(x, xs, mu):
    return x + (xs - x) * mu            # lerp between current and shifted


def rwkv_time_mix(params, x, cfg: ModelConfig, cache=None):
    """x (B,L,D) -> (y, new_cache); cache = {"shift": (B,1,D), "state": (B,H,K,K)}."""
    B, L, D = x.shape
    H, K = rwkv_dims(cfg)
    cd = jnp.dtype(cfg.compute_dtype)
    xs = _token_shift(x, cache["shift"] if cache is not None else None)

    def proj(name):
        return jnp.einsum("bld,de->ble",
                          _mix(x, xs, params["mu_" + name[1]]),
                          params[name].astype(cd))

    r = proj("wr").reshape(B, L, H, K)
    k = proj("wk").reshape(B, L, H, K)
    v = proj("wv").reshape(B, L, H, K)
    g = jax.nn.silu(proj("wg"))

    # data-dependent decay (the Finch contribution): w = exp(-exp(...))
    xw = _mix(x, xs, params["mu_w"])
    lora = jnp.einsum("bld,dr->blr", xw, params["w_lora_a"].astype(cd))
    lora = jnp.einsum("blr,rd->bld", jnp.tanh(lora),
                      params["w_lora_b"].astype(cd))
    w_raw = params["w0"].astype(jnp.float32) + lora.astype(jnp.float32)
    log_w = jnp.maximum(-jnp.exp(w_raw), MIN_LOG_W).reshape(B, L, H, K)

    state = cache["state"] if cache is not None else None
    if L == 1 and cache is not None:
        y, s = wkv_ops.wkv6_step(r[:, 0], k[:, 0], v[:, 0], log_w[:, 0],
                                 params["u"], state)
        y = y[:, None]
    else:
        y, s = wkv_ops.wkv6(r, k, v, log_w.astype(cd), params["u"], state,
                            chunk=min(cfg.attn_chunk, 64))

    y = y.reshape(B, L, D)
    y = rmsnorm({"scale": params["ln_x"]}, y, cfg.norm_eps) * g
    out = jnp.einsum("bld,de->ble", y, params["wo"].astype(cd))
    new_cache = None
    if cache is not None:
        new_cache = {"shift": x[:, -1:], "state": s}
    return out, new_cache


def rwkv_channel_mix(params, x, cfg: ModelConfig, cache=None):
    """Squared-ReLU channel mix; cache = {"shift": (B,1,D)}."""
    cd = jnp.dtype(cfg.compute_dtype)
    xs = _token_shift(x, cache["shift"] if cache is not None else None)
    kx = _mix(x, xs, params["mu_k"])
    h = jnp.square(jax.nn.relu(
        jnp.einsum("bld,df->blf", kx, params["wk"].astype(cd))))
    out = jnp.einsum("blf,fd->bld", h, params["wv"].astype(cd))
    new_cache = {"shift": x[:, -1:]} if cache is not None else None
    return out, new_cache


def init_rwkv_cache(cfg: ModelConfig, batch: int, layers: int, dtype=None):
    H, K = rwkv_dims(cfg)
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    return {
        "tm_shift": jnp.zeros((layers, batch, 1, cfg.d_model), dt),
        "cm_shift": jnp.zeros((layers, batch, 1, cfg.d_model), dt),
        "state": jnp.zeros((layers, batch, H, K, K), jnp.float32),
    }
