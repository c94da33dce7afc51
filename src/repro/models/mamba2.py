"""Mamba2 block (zamba2 backbone): projections + causal conv + SSD scan.

Layout follows the Mamba2 paper: a fused input projection producing
(z gate, x, B, C, dt), a depthwise causal conv over (x, B, C), the SSD
recurrence (repro.kernels.mamba2_ssd), a gated RMSNorm and an output
projection.  Decode carries {conv_state, ssm_state}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.mamba2_ssd import ops as ssd_ops
from repro.models.layers import rmsnorm

# log-decay clamp: keeps exp() terms finite in every implementation
MIN_LOG_A = -12.0


def mamba_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads or d_inner // 64          # head dim P = 64 by default
    P = d_inner // H
    G = 1                                        # single B/C group
    return d_inner, H, P, G


def mamba_params(mk, cfg: ModelConfig, stacked=()):
    d = cfg.d_model
    d_inner, H, P, G = mamba_dims(cfg)
    N, W = cfg.ssm_state, cfg.ssm_conv
    conv_ch = d_inner + 2 * G * N
    proj_out = 2 * d_inner + 2 * G * N + H      # z, x, B, C, dt
    lead = tuple("layer" for _ in stacked)
    return {
        "in_proj": mk.param(stacked + (d, proj_out),
                            lead + ("embed", "ssm_inner"), fan_in=d),
        "conv_w": mk.param(stacked + (W, conv_ch),
                           lead + ("conv", "ssm_inner"), scale=0.5),
        "conv_b": mk.param(stacked + (conv_ch,),
                           lead + ("ssm_inner",), init="zeros"),
        "a_log": mk.param(stacked + (H,), lead + ("ssm_heads",), init="ones"),
        "dt_bias": mk.param(stacked + (H,), lead + ("ssm_heads",), init="zeros"),
        "d_skip": mk.param(stacked + (H,), lead + ("ssm_heads",), init="ones"),
        "norm": mk.param(stacked + (d_inner,), lead + ("ssm_inner",),
                         init="ones"),
        "out_proj": mk.param(stacked + (d_inner, d),
                             lead + ("ssm_inner", "embed"), fan_in=d_inner),
    }


def _split_proj(zxbcdt, cfg):
    d_inner, H, P, G = mamba_dims(cfg)
    N = cfg.ssm_state
    z, xin, b, c, dt = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + G * N, 2 * d_inner + 2 * G * N],
        axis=-1)
    return z, xin, b, c, dt


def _causal_conv(x, w, b, conv_state=None):
    """Depthwise causal conv. x (B,L,C), w (W,C). Returns (y, new_state)
    where state is the last W-1 inputs (B, W-1, C)."""
    W = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)           # (B, L+W-1, C)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):] if W > 1 else pad[:, :0]
    return y + b, new_state


def _ssm_inputs(params, xin_c, b_c, c_c, dt_raw, cfg):
    """Common post-conv plumbing: activations + dt/decay computation."""
    d_inner, H, P, G = mamba_dims(cfg)
    N = cfg.ssm_state
    xin_c = jax.nn.silu(xin_c)
    b_c = jax.nn.silu(b_c)
    c_c = jax.nn.silu(c_c)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (...,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))              # (H,) < 0
    log_a = jnp.maximum(dt * a, MIN_LOG_A)                         # (...,H)
    return xin_c, b_c, c_c, dt, log_a


def mamba_block(params, x, cfg: ModelConfig, cache=None):
    """x (B,L,D) -> (y (B,L,D), new_cache).

    cache: None (training/prefill from scratch) or
    {"conv": (B,W-1,C), "ssm": (B,H,P,N)}; L may be 1 (decode) or more.
    """
    B, L, D = x.shape
    d_inner, H, P, G = mamba_dims(cfg)
    N = cfg.ssm_state
    cd = jnp.dtype(cfg.compute_dtype)

    zxbcdt = jnp.einsum("bld,dk->blk", x, params["in_proj"].astype(cd))
    z, xin, b, c, dt_raw = _split_proj(zxbcdt, cfg)
    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(
        conv_in, params["conv_w"].astype(cd), params["conv_b"].astype(cd),
        conv_state)
    xin_c, b_c, c_c = jnp.split(conv_out, [d_inner, d_inner + G * N], axis=-1)
    xin_c, b_c, c_c, dt, log_a = _ssm_inputs(params, xin_c, b_c, c_c,
                                             dt_raw, cfg)

    xh = (xin_c.astype(jnp.float32).reshape(B, L, H, P)
          * dt[..., None]).astype(cd)                       # dt-scaled input
    bg = b_c.reshape(B, L, G, N)
    cg = c_c.reshape(B, L, G, N)
    s0 = cache["ssm"] if cache is not None else None

    if L == 1 and cache is not None:
        y, s = ssd_ops.ssd_step(xh[:, 0], log_a[:, 0], bg[:, 0], cg[:, 0], s0)
        y = y[:, None]
    else:
        y, s = ssd_ops.ssd(xh, log_a.astype(cd), bg, cg, s0,
                           chunk=min(cfg.attn_chunk, 128))

    y = y.astype(jnp.float32) + (params["d_skip"].astype(jnp.float32)[:, None]
                                 * xin_c.astype(jnp.float32).reshape(B, L, H, P))
    y = y.reshape(B, L, d_inner).astype(cd)
    y = rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("blk,kd->bld", y, params["out_proj"].astype(cd))
    new_cache = {"conv": new_conv, "ssm": s} if cache is not None else None
    return out, new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, layers: int, dtype=None):
    d_inner, H, P, G = mamba_dims(cfg)
    N, W = cfg.ssm_state, cfg.ssm_conv
    conv_ch = d_inner + 2 * G * N
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    return {
        "conv": jnp.zeros((layers, batch, W - 1, conv_ch), dt),
        "ssm": jnp.zeros((layers, batch, H, P, N), jnp.float32),
    }
