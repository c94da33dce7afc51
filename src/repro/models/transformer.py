"""Decoder-only transformer stacks for every assigned LM family.

One parameter tree + one apply path per family, all scanned over layers with
``lax.scan`` (stacked parameters, small HLO).  Families:

- dense    : [granite-20b, qwen3-8b, internlm2-1.8b, qwen2-vl backbone]
- gemma2   : alternating local/global attention, sandwich norms, softcaps
- moe      : [kimi-k2, llama4-scout] capacity-routed expert FFN
- zamba2   : Mamba2 backbone + one *shared* attention block every N layers
- rwkv     : RWKV6 attention-free time mix / channel mix

The same block functions serve train (no cache), prefill (collect cache) and
decode (consume + update cache); caches are stacked over layers so they flow
through the scans as xs/ys, except the dense/MoE decode KV cache, which rides
in the scan's loop state and is updated in place (`_scan_kv_in_place`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2, rwkv6
from repro.models.layers import rmsnorm, rmsnorm_params, rope_cos_sin
from repro.models.mlp import mlp, mlp_params
from repro.models.moe import moe_ffn, moe_params


# ---------------------------------------------------------------------------
# Block parameter trees
# ---------------------------------------------------------------------------


def dense_block_params(mk, cfg: ModelConfig, stacked=(), moe: bool = False,
                       cross: bool = False):
    p = {
        "ln1": rmsnorm_params(mk, cfg.d_model, stacked),
        "attn": attn.attention_params(mk, cfg, stacked),
        "ln2": rmsnorm_params(mk, cfg.d_model, stacked),
        "ffn": (moe_params(mk, cfg, stacked) if moe
                else mlp_params(mk, cfg, stacked)),
    }
    if cross:
        p["ln_cross"] = rmsnorm_params(mk, cfg.d_model, stacked)
        p["cross"] = attn.attention_params(mk, cfg, stacked, cross=True)
    if cfg.post_norm:
        p["ln1_post"] = rmsnorm_params(mk, cfg.d_model, stacked)
        p["ln2_post"] = rmsnorm_params(mk, cfg.d_model, stacked)
    return p


def rwkv_block_params(mk, cfg: ModelConfig, stacked=()):
    return {
        "ln1": rmsnorm_params(mk, cfg.d_model, stacked),
        "tmix": rwkv6.rwkv_time_mix_params(mk, cfg, stacked),
        "ln2": rmsnorm_params(mk, cfg.d_model, stacked),
        "cmix": rwkv6.rwkv_channel_mix_params(mk, cfg, stacked),
    }


def mamba_block_params(mk, cfg: ModelConfig, stacked=()):
    return {
        "ln": rmsnorm_params(mk, cfg.d_model, stacked),
        "mamba": mamba2.mamba_params(mk, cfg, stacked),
    }


# ---------------------------------------------------------------------------
# Block applications.  All return (h, new_cache, aux_loss).
# ---------------------------------------------------------------------------


def _maybe_post(p, name, y, cfg):
    return rmsnorm(p[name], y, cfg.norm_eps) if cfg.post_norm else y


def _residual(h):
    """Between-block residual stream: batch-sharded, replicated over the
    model axis."""
    from repro.distributed import axisenv
    return axisenv.constrain(h, "batch", None, None)


def apply_dense_block(p, h, cfg: ModelConfig, *, cos, sin, window=None,
                      causal=True, cache=None, cur_len=None, enc_kv=None,
                      collect_cache=False, layer=None):
    a_in = rmsnorm(p["ln1"], h, cfg.norm_eps)
    if collect_cache:
        q, k, v = attn.project_qkv(p["attn"], a_in, cfg, cos, sin)
        o = attn.attend(q, k, v, cfg=cfg, causal=causal, window=window)
        a_out = attn.output_proj(p["attn"], o, cfg)
        new_cache = {"k": k, "v": v}
    else:
        a_out, new_cache = attn.self_attention(
            p["attn"], a_in, cfg, cos=cos, sin=sin, causal=causal,
            window=window, cache=cache, cur_len=cur_len, layer=layer)
    h = _residual(h + _maybe_post(p, "ln1_post", a_out, cfg))

    if enc_kv is not None:
        c_in = rmsnorm(p["ln_cross"], h, cfg.norm_eps)
        h = _residual(h + attn.cross_attention(p["cross"], c_in, enc_kv,
                                               cfg))

    m_in = rmsnorm(p["ln2"], h, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "router" in p["ffn"]:
        m_out, aux = moe_ffn(p["ffn"], m_in, cfg)
    else:
        m_out = mlp(p["ffn"], m_in, cfg)
    h = _residual(h + _maybe_post(p, "ln2_post", m_out, cfg))
    return h, new_cache, aux


def apply_rwkv_block(p, h, cfg: ModelConfig, cache=None):
    tm_cache = cm_cache = None
    if cache is not None:
        tm_cache = {"shift": cache["tm_shift"], "state": cache["state"]}
        cm_cache = {"shift": cache["cm_shift"]}
    t_out, tm_new = rwkv6.rwkv_time_mix(
        p["tmix"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg, tm_cache)
    h = h + t_out
    c_out, cm_new = rwkv6.rwkv_channel_mix(
        p["cmix"], rmsnorm(p["ln2"], h, cfg.norm_eps), cfg, cm_cache)
    h = h + c_out
    new_cache = None
    if cache is not None:
        new_cache = {"tm_shift": tm_new["shift"], "state": tm_new["state"],
                     "cm_shift": cm_new["shift"]}
    return h, new_cache, jnp.zeros((), jnp.float32)


def apply_mamba_block(p, h, cfg: ModelConfig, cache=None):
    m_out, new_cache = mamba2.mamba_block(
        p["mamba"], rmsnorm(p["ln"], h, cfg.norm_eps), cfg, cache)
    return h + m_out, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Stacks.  params["blocks"] layout depends on the family (see builders).
# ---------------------------------------------------------------------------


def _ckpt(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "policy":
        # save matmul outputs; recompute only cheap elementwise work in the
        # backward pass (vs "block", which recomputes the full forward)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def stack_params(mk, cfg: ModelConfig):
    """Stacked block parameters for the decoder stack of `cfg`."""
    L = cfg.num_layers
    if cfg.rwkv:
        return {"rwkv": rwkv_block_params(mk, cfg, stacked=(L,))}
    if cfg.family == "hybrid":
        ae = max(cfg.attn_every, 1)
        groups, tail = divmod(L, ae)
        p = {"mamba_main": mamba_block_params(mk, cfg, stacked=(groups, ae)),
             "shared_attn": dense_block_params(mk, cfg)}
        if tail:
            p["mamba_tail"] = mamba_block_params(mk, cfg, stacked=(tail,))
        return p
    if cfg.local_global_period:
        per = cfg.local_global_period
        assert L % per == 0, (L, per)
        return {"lg": dense_block_params(mk, cfg, stacked=(L // per, per),
                                         moe=cfg.is_moe)}
    return {"uniform": dense_block_params(mk, cfg, stacked=(L,),
                                          moe=cfg.is_moe)}


def _scan_uniform(params, h, cfg, apply_fn, cache, collect):
    """Generic scan over a (L, ...)-stacked block group."""
    def body(carry, xs):
        h, aux = carry
        p, c = xs
        h, new_c, a = apply_fn(p, h, c)
        return (h, aux + a), new_c

    body = _ckpt(body, cfg)
    L = jax.tree.leaves(params)[0].shape[0]
    xs = (params, cache)
    if cache is None and not collect:
        xs = (params, None)
    (h, aux), new_cache = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                       xs, length=L)
    return h, new_cache, aux


def _scan_kv_in_place(params, h, apply_fn, cache):
    """Decode over an (L, ...)-stacked block group whose stacked KV cache is
    carried in the loop state: layer i writes only its new positions and
    reads its slice in place, where xs/ys would copy each layer's whole K/V
    out of the stack and write it back every step."""
    def body(carry, xs):
        h, aux, c = carry
        p, i = xs
        h, c, a = apply_fn(p, h, c, i)
        return (h, aux + a, c), None

    L = jax.tree.leaves(params)[0].shape[0]
    (h, aux, cache), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32), cache),
        (params, jnp.arange(L, dtype=jnp.int32)), length=L)
    return h, cache, aux


def run_stack(params, h, cfg: ModelConfig, *, cos, sin, cache=None,
              cur_len=None, collect_cache=False):
    """Run the decoder stack.  Returns (h, new_cache, aux_loss).

    cache trees are stacked over layers; `collect_cache` makes a fresh cache
    from a full forward pass (prefill)."""
    if cfg.rwkv:
        def app(p, x, c):
            return apply_rwkv_block(p, x, cfg, c)
        if collect_cache:
            cache = rwkv6.init_rwkv_cache(cfg, h.shape[0], cfg.num_layers)
        return _scan_uniform(params["rwkv"], h, cfg, app, cache,
                             collect_cache)

    if cfg.family == "hybrid":
        return _run_zamba_stack(params, h, cfg, cos=cos, sin=sin, cache=cache,
                                cur_len=cur_len, collect_cache=collect_cache)

    if cfg.local_global_period:
        return _run_local_global_stack(params, h, cfg, cos=cos, sin=sin,
                                       cache=cache, cur_len=cur_len,
                                       collect_cache=collect_cache)

    if cache is not None:
        def app_decode(p, x, c, i):
            return apply_dense_block(p, x, cfg, cos=cos, sin=sin, cache=c,
                                     cur_len=cur_len, layer=i)
        return _scan_kv_in_place(params["uniform"], h, app_decode, cache)

    def app(p, x, c):
        return apply_dense_block(p, x, cfg, cos=cos, sin=sin, cache=c,
                                 cur_len=cur_len,
                                 collect_cache=collect_cache)
    return _scan_uniform(params["uniform"], h, cfg, app, cache, collect_cache)


def _run_local_global_stack(params, h, cfg, *, cos, sin, cache, cur_len,
                            collect_cache):
    """gemma2: period-P pattern, sub-layer i of each step has its own window.
    Convention: the *last* layer of each period is global; the rest local."""
    per = cfg.local_global_period
    windows = [cfg.sliding_window] * (per - 1) + [None]

    def body(carry, xs):
        h, aux = carry
        p, c = xs
        new_cs = []
        for i in range(per):
            pi = jax.tree.map(lambda t: t[i], p)
            ci = None if c is None else jax.tree.map(lambda t: t[i], c)
            h, nc, a = apply_dense_block(
                pi, h, cfg, cos=cos, sin=sin, window=windows[i], cache=ci,
                cur_len=cur_len, collect_cache=collect_cache)
            aux = aux + a
            new_cs.append(nc)
        stacked_c = (None if new_cs[0] is None else
                     jax.tree.map(lambda *t: jnp.stack(t), *new_cs))
        return (h, aux), stacked_c

    body = _ckpt(body, cfg)
    n_steps = cfg.num_layers // per
    # reshape stacked caches (L, ...) -> (n_steps, per, ...)
    c_in = cache
    if cache is not None:
        c_in = jax.tree.map(
            lambda t: t.reshape((n_steps, per) + t.shape[1:]), cache)
    (h, aux), new_cache = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), (params["lg"], c_in),
        length=n_steps)
    if new_cache is not None:
        new_cache = jax.tree.map(
            lambda t: t.reshape((cfg.num_layers,) + t.shape[2:]), new_cache)
    return h, new_cache, aux


def _run_zamba_stack(params, h, cfg, *, cos, sin, cache, cur_len,
                     collect_cache):
    """zamba2: groups of `attn_every` Mamba2 blocks, each followed by the
    SHARED attention block (same params, per-application KV cache)."""
    ae = max(cfg.attn_every, 1)
    groups, tail = divmod(cfg.num_layers, ae)
    shared_p = params["shared_attn"]

    def mamba_app(p, x, c):
        return apply_mamba_block(p, x, cfg, c)

    def group_body(carry, xs):
        h, aux = carry
        p_grp, c_mamba, c_attn = xs
        h, new_m, a1 = _scan_uniform(p_grp, h, cfg, mamba_app, c_mamba,
                                     collect_cache)
        h, new_a, a2 = apply_dense_block(
            shared_p, h, cfg, cos=cos, sin=sin, cache=c_attn,
            cur_len=cur_len, collect_cache=collect_cache)
        return (h, aux + a1 + a2), (new_m, new_a)

    group_body = _ckpt(group_body, cfg)

    c_mamba_main = c_mamba_tail = c_attn = None
    if cache is not None:
        c_mamba_main = jax.tree.map(
            lambda t: t[:groups * ae].reshape((groups, ae) + t.shape[1:]),
            cache["mamba"])
        if tail:
            c_mamba_tail = jax.tree.map(lambda t: t[groups * ae:],
                                        cache["mamba"])
        c_attn = cache["attn"]
    elif collect_cache:
        # prefill: mamba states start from zeros (block updates them);
        # attention KV is *collected* fresh, so no input cache is needed.
        B = h.shape[0]
        full = mamba2.init_mamba_cache(cfg, B, cfg.num_layers)
        c_mamba_main = jax.tree.map(
            lambda t: t[:groups * ae].reshape((groups, ae) + t.shape[1:]),
            full)
        if tail:
            c_mamba_tail = jax.tree.map(lambda t: t[groups * ae:], full)
        c_attn = None

    (h, aux), (new_m, new_a) = jax.lax.scan(
        group_body, (h, jnp.zeros((), jnp.float32)),
        (params["mamba_main"], c_mamba_main, c_attn), length=groups)

    new_mamba = jax.tree.map(
        lambda t: t.reshape((groups * ae,) + t.shape[2:]), new_m)
    if tail:
        h, new_tail, a3 = _scan_uniform(params["mamba_tail"], h, cfg,
                                        mamba_app, c_mamba_tail,
                                        collect_cache)
        aux = aux + a3
        if new_tail is not None:
            new_mamba = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), new_mamba, new_tail)

    new_cache = None
    if new_mamba is not None and new_a is not None:
        new_cache = {"mamba": new_mamba, "attn": new_a}
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache pytree for the decoder stack (stacked over layers)."""
    if cfg.rwkv:
        return rwkv6.init_rwkv_cache(cfg, batch, cfg.num_layers)
    if cfg.family == "hybrid":
        ae = max(cfg.attn_every, 1)
        groups = cfg.num_layers // ae
        return {
            "mamba": mamba2.init_mamba_cache(cfg, batch, cfg.num_layers),
            "attn": attn.init_kv_cache(cfg, batch, max_len, groups),
        }
    return attn.init_kv_cache(cfg, batch, max_len, cfg.num_layers)


def positions_for(cfg: ModelConfig, batch: int, seq: int, offset=0):
    pos = jnp.arange(seq)[None, :] + offset
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


def rope_tables(cfg: ModelConfig, positions):
    if cfg.rwkv:
        return None, None
    return rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta,
                        cfg.mrope_sections)
