"""Mixture-of-Experts FFN with capacity-based expert-parallel dispatch.

Three reference implementations, selected by ``cfg.moe_impl``:

- ``dropping`` (default): scatter/gather dispatch.  Tokens are grouped per
  batch row; each row scatters its token *indices* into an (E, C) slot table
  (cheap int scatter), gathers token activations into (E, C, D), runs the
  per-expert FFN, and gathers results back.  Cost is O(T*D) data movement +
  the expert GEMMs -- no O(T*E*C*D) one-hot einsums.  Over-capacity tokens
  are dropped (the residual stream passes them through), matching GShard /
  Switch semantics.
- ``einsum``: the classic GShard one-hot dispatch/combine einsums.  Exact
  same semantics as ``dropping``; costs O(T*E*C*D) so only viable for tiny
  shapes.  Used as the oracle in tests.
- ``dense``: computes every expert for every token and mixes with router
  weights (no capacity, no drops).  Tiny smoke configs only.

A grouped-matmul Pallas kernel (repro.kernels.moe_gmm) implements the sorted
per-expert FFN for the TPU path (``moe_impl="gmm"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import axisenv
from repro.models.mlp import _act


def moe_params(mk, cfg: ModelConfig, stacked=()):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    lead = tuple("layer" for _ in stacked)
    return {
        "router": mk.param(stacked + (d, e), lead + ("embed", "experts"),
                           fan_in=d),
        "wi_gate": mk.param(stacked + (e, d, f),
                            lead + ("experts", "embed", "ff"), fan_in=d),
        "wi_up": mk.param(stacked + (e, d, f),
                          lead + ("experts", "embed", "ff"), fan_in=d),
        "wo": mk.param(stacked + (e, f, d),
                       lead + ("experts", "ff", "embed"), fan_in=f),
    }


def _router(params, x, cfg: ModelConfig):
    """x (..., D) -> (gates (...,E) f32, topw (...,k), topi (...,k))."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, cfg.num_experts_per_token)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    return gates, topw, topi


def aux_load_balance_loss(gates, topi, num_experts: int):
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    oh = jax.nn.one_hot(topi, num_experts, dtype=jnp.float32)  # (...,k,E)
    frac_tokens = jnp.mean(jnp.sum(oh, axis=-2).reshape(-1, num_experts),
                           axis=0)
    frac_prob = jnp.mean(gates.reshape(-1, num_experts), axis=0)
    return num_experts * jnp.sum(frac_tokens * frac_prob)


def _expert_ffn(params, xe, cfg: ModelConfig):
    """xe (E, C, D) -> (E, C, D); per-expert gated MLP."""
    cd = jnp.dtype(cfg.compute_dtype)
    act = _act(cfg.act)
    g = jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", xe, params["wi_up"].astype(cd))
    return jnp.einsum("ecf,efd->ecd", act(g) * u, params["wo"].astype(cd))


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(max(1, round(cfg.num_experts_per_token * tokens_per_group
                         / cfg.num_experts * cfg.capacity_factor)))
    if c > 128:
        c = -(-c // 128) * 128       # lane-friendly rounding when large
    return c


def _route_positions(topi, cfg: ModelConfig, capacity: int):
    """topi (S, K) expert ids -> (pos (S,K) slot-in-expert, keep (S,K)).

    Assignment priority is k-slot major: every token's top-1 choice wins
    capacity before any token's top-2 choice, matching GShard."""
    S, K = topi.shape
    E = cfg.num_experts
    oh = jax.nn.one_hot(topi, E, dtype=jnp.int32)             # (S,K,E)
    oh_km = jnp.transpose(oh, (1, 0, 2)).reshape(K * S, E)
    pos_km = jnp.cumsum(oh_km, axis=0) - oh_km
    pos = pos_km.reshape(K, S, E).transpose(1, 0, 2)          # (S,K,E)
    pos = jnp.sum(pos * oh, axis=-1)                          # (S,K)
    keep = pos < capacity
    return pos, keep


def moe_dropping(params, x, cfg: ModelConfig):
    """Scatter/gather dispatch. x (B,S,D) -> (y (B,S,D), aux_loss).

    Written vmap-free (batched scatters/gathers) so the expert-parallel
    sharding constraints on the (B, E, C, D) expert buffers apply: with
    experts on the "model" axis and batch on "data", GSPMD lowers the
    gather -> expert-FFN -> gather-back path as the canonical EP
    all-to-all pair instead of replicating expert inputs."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_token
    C = _capacity(cfg, S)
    cd = jnp.dtype(cfg.compute_dtype)

    gates, topw, topi = _router(params, x, cfg)               # (B,S,E/K)
    aux = aux_load_balance_loss(gates, topi, E)

    pos, keep = jax.vmap(lambda t: _route_positions(t, cfg, C))(topi)
    e_flat = topi.reshape(B, S * K)
    p_flat = jnp.where(keep, pos, C).reshape(B, S * K)        # C = dropped
    tok_flat = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, K)
    ).reshape(B, S * K)
    b_idx = jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[:, None], (B, S * K))

    # (B, E, C) slot table of source-token indices; empty slots -> S (OOB)
    slots = jnp.full((B, E, C), S, jnp.int32)
    slots = slots.at[b_idx, e_flat, p_flat].set(tok_flat, mode="drop")

    # batched gather into expert slots: empty slots hold the OOB index S and
    # read zeros via fill-mode.  (The old pad-row concat grew the token
    # axis, which is sharded over "model" under seq-parallelism -- a
    # concat+split across a sharded dim, which GSPMD has miscompiled; a
    # fill-mode gather never changes the sharded shape.)
    xe = jnp.take_along_axis(
        x, slots.reshape(B, E * C)[..., None], axis=1,
        mode="fill", fill_value=0,
    ).reshape(B, E, C, D).astype(cd)
    xe = axisenv.constrain(xe, "batch", "model", None, None)  # EP a2a here
    ye = _expert_ffn_batched(params, xe, cfg)                 # (B,E,C,D)
    ye = axisenv.constrain(ye, "batch", "model", None, None)

    # combine: gather each kept assignment's slot back (a2a back here).
    # Dropped assignments point at the OOB index E*C and read zeros via
    # fill-mode -- the E*C axis merges the "model"-sharded expert dim, so
    # appending a pad row here was the second instance of the sharded-dim
    # concat pattern.
    yk = ye.reshape(B, E * C, D)
    flat_idx = jnp.where(keep.reshape(B, S * K),
                         e_flat * C + p_flat, E * C)          # OOB = dropped
    y_sel = jnp.take_along_axis(yk, flat_idx[..., None], axis=1,
                                mode="fill", fill_value=0)
    w = (topw.reshape(B, S * K, 1)
         * keep.reshape(B, S * K, 1)).astype(y_sel.dtype)
    y = jnp.sum((y_sel * w).reshape(B, S, K, D), axis=2)
    y = axisenv.constrain(y, "batch", None, None)
    return y.astype(x.dtype), aux


def _expert_ffn_batched(params, xe, cfg: ModelConfig):
    """xe (B, E, C, D) -> (B, E, C, D); per-expert gated MLP."""
    cd = jnp.dtype(cfg.compute_dtype)
    act = _act(cfg.act)
    g = jnp.einsum("becd,edf->becf", xe, params["wi_gate"].astype(cd))
    u = jnp.einsum("becd,edf->becf", xe, params["wi_up"].astype(cd))
    return jnp.einsum("becf,efd->becd", act(g) * u,
                      params["wo"].astype(cd))


def moe_einsum(params, x, cfg: ModelConfig):
    """GShard one-hot dispatch/combine einsums (oracle; tiny shapes only)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_token
    C = _capacity(cfg, S)
    cd = jnp.dtype(cfg.compute_dtype)
    gates, topw, topi = _router(params, x, cfg)
    aux = aux_load_balance_loss(gates, topi, E)

    def row(x_row, topi_row, topw_row):
        pos, keep = _route_positions(topi_row, cfg, C)
        ohf = (jax.nn.one_hot(topi_row, E) * keep[..., None])  # (S,K,E)
        slot = jax.nn.one_hot(pos, C)                          # (S,K,C)
        dispatch = jnp.einsum("ske,skc->sec", ohf, slot)
        combine = jnp.einsum("ske,skc,sk->sec", ohf, slot,
                             topw_row.astype(jnp.float32))
        xe = jnp.einsum("sd,sec->ecd", x_row.astype(jnp.float32),
                        dispatch).astype(cd)
        ye = _expert_ffn(params, xe, cfg)
        return jnp.einsum("ecd,sec->sd", ye.astype(jnp.float32), combine)

    y = jax.vmap(row)(x, topi, topw)
    return y.astype(x.dtype), aux


def moe_dense(params, x, cfg: ModelConfig):
    """Exact MoE: every expert for every token (tiny configs only)."""
    B, S, D = x.shape
    E = cfg.num_experts
    xt = x.reshape(B * S, D)
    gates, topw, topi = _router(params, xt, cfg)
    aux = aux_load_balance_loss(gates, topi, E)
    mix = jnp.sum(jax.nn.one_hot(topi, E) * topw[..., None], axis=1)  # (T,E)
    cd = jnp.dtype(cfg.compute_dtype)
    xe = jnp.broadcast_to(xt[None], (E,) + xt.shape).astype(cd)
    ye = _expert_ffn(params, xe, cfg)                         # (E,T,D)
    y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), mix)
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_gmm(params, x, cfg: ModelConfig):
    """Sorted grouped-matmul path backed by the Pallas kernel."""
    from repro.kernels.moe_gmm import ops as gmm_ops
    return gmm_ops.moe_ffn(params, x, cfg)


def moe_ep(params, x, cfg: ModelConfig):
    """shard_map expert-parallel all_to_all path (perf lever); falls back
    to the GSPMD scatter/gather path when the mesh/shape does not fit
    (no model axis, S or E not divisible, decode with S=1)."""
    env = axisenv._env()
    mesh = env.get("mesh") if env else None
    tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    if (mesh is None or tp <= 1 or x.shape[1] % tp
            or cfg.num_experts % tp):
        return moe_dropping(params, x, cfg)
    from repro.models import moe_ep as ep
    return ep.moe_ep_a2a(params, x, cfg, mesh, env["batch"])


def moe_ffn(params, x, cfg: ModelConfig):
    impl = {"dropping": moe_dropping, "einsum": moe_einsum,
            "dense": moe_dense, "gmm": moe_gmm, "ep_a2a": moe_ep}
    return impl[cfg.moe_impl](params, x, cfg)
