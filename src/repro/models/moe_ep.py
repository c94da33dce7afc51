"""Expert-parallel MoE via shard_map + explicit all_to_all (moe_impl="ep_a2a").

Why: under pure GSPMD the gather-based dispatch/combine lowers to
all-gathers of the (E, C, D) expert buffers plus a giant scatter-add
all-reduce in the backward pass (~94 GB/layer/device wire for kimi-k2 at
train_4k -- measured, see EXPERIMENTS.md §Perf).  The canonical EP lowering
moves only the routed token activations, twice:

  tokens (seq-sharded over the model axis)
    -> route locally -> per-destination-rank send buffers
    -> all_to_all over "model" (dispatch)
    -> local capacity dispatch to this rank's E/TP experts -> expert FFN
    -> results written back into the mirrored slot layout
    -> all_to_all back (combine) -> weighted sum per token.

Per-layer wire: 2 x T_local*K*D*bf16 per device (~0.9 GB for kimi) instead
of ~94 GB.  Works with the seq-parallel residual layout (tokens already
sharded over "model"); requires S % TP == 0, falling back to the GSPMD path
otherwise (e.g. decode with S=1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import axisenv
from repro.models.mlp import _act


def _round_up(x, m):
    return -(-x // m) * m


def _positions_in_group(group_ids, num_groups, capacity):
    """group_ids (A,) -> (pos (A,), keep (A,)): slot within each group,
    assignment order = index order."""
    oh = jax.nn.one_hot(group_ids, num_groups, dtype=jnp.int32)   # (A,G)
    pos = jnp.cumsum(oh, axis=0) - oh
    pos = jnp.sum(pos * oh, axis=-1)
    return pos, pos < capacity


def moe_ep_a2a(params, x, cfg: ModelConfig, mesh, batch_axes):
    """x (B, S, D) -> (y, aux). Requires an ambient mesh with a "model"
    axis dividing S and cfg.num_experts."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_token
    tp = int(mesh.shape["model"])
    E_loc = E // tp
    S_loc = S // tp
    dp = 1
    for a in (batch_axes or ()):
        dp *= int(mesh.shape[a])
    T_loc = (B // dp) * S_loc                      # per-DEVICE tokens
    A = T_loc * K                                  # local assignments
    # capacity of each rank->rank send lane and of each local expert
    C_send = _round_up(int(A / tp * cfg.capacity_factor) + 1, 8)
    C_e = _round_up(int(tp * C_send / E_loc * cfg.capacity_factor) + 1, 8)
    cd = jnp.dtype(cfg.compute_dtype)
    act = _act(cfg.act)

    bax = tuple(batch_axes) if batch_axes else None
    all_axes = tuple(mesh.shape.keys())
    in_specs = (
        P(bax, "model", None),                     # x: seq-sharded
        P(None, None),                             # router (replicated)
        P("model", None, None),                    # wi_gate
        P("model", None, None),                    # wi_up
        P("model", None, None),                    # wo
    )
    out_specs = (P(bax, "model", None), P())

    def body(x_loc, router, wi_g, wi_u, wo):
        # x_loc: (B_loc, S_loc, D) -- per-device block
        b_loc = x_loc.shape[0]
        t_loc = b_loc * S_loc
        a_loc = t_loc * K
        xt = x_loc.reshape(t_loc, D)

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(gates, K)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

        # Switch aux loss over local tokens (mean of means == global mean)
        oh = jax.nn.one_hot(topi, E, dtype=jnp.float32)
        aux = E * jnp.sum(jnp.mean(jnp.sum(oh, 1), 0) * jnp.mean(gates, 0))

        # ---- dispatch: build per-destination-rank send lanes ----
        e_flat = topi.reshape(a_loc)                       # global expert id
        dest = e_flat // E_loc                             # owning rank
        pos, keep = _positions_in_group(dest, tp, C_send)
        tok = jnp.broadcast_to(
            jnp.arange(t_loc, dtype=jnp.int32)[:, None],
            (t_loc, K)).reshape(a_loc)

        slot_tok = jnp.full((tp, C_send), t_loc, jnp.int32)
        slot_tok = slot_tok.at[dest, jnp.where(keep, pos, C_send)].set(
            tok, mode="drop")
        slot_eid = jnp.full((tp, C_send), E_loc, jnp.int32)
        slot_eid = slot_eid.at[dest, jnp.where(keep, pos, C_send)].set(
            (e_flat % E_loc).astype(jnp.int32), mode="drop")

        xt_pad = jnp.concatenate(
            [xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        send_x = jnp.take(xt_pad, slot_tok, axis=0).astype(cd)  # (tp,Cs,D)

        recv_x = jax.lax.all_to_all(
            send_x.reshape(tp * C_send, D), "model", 0, 0, tiled=True
        ).reshape(tp, C_send, D)
        recv_eid = jax.lax.all_to_all(
            slot_eid.reshape(tp * C_send), "model", 0, 0, tiled=True
        ).reshape(tp, C_send)

        # ---- local capacity dispatch to my E_loc experts ----
        r_eid = recv_eid.reshape(tp * C_send)
        valid = r_eid < E_loc
        epos, ekeep = _positions_in_group(
            jnp.where(valid, r_eid, E_loc), E_loc + 1, C_e)
        ekeep = ekeep & valid
        eslot = jnp.full((E_loc, C_e), tp * C_send, jnp.int32)
        eslot = eslot.at[jnp.where(valid, r_eid, E_loc),
                         jnp.where(ekeep, epos, C_e)].set(
            jnp.arange(tp * C_send, dtype=jnp.int32), mode="drop")
        rx_pad = jnp.concatenate(
            [recv_x.reshape(tp * C_send, D),
             jnp.zeros((1, D), recv_x.dtype)], axis=0)
        xe = jnp.take(rx_pad, eslot, axis=0)               # (E_loc, C_e, D)

        # ---- expert FFN (this rank's experts) ----
        g = jnp.einsum("ecd,edf->ecf", xe, wi_g.astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe, wi_u.astype(cd))
        ye = jnp.einsum("ecf,efd->ecd", act(g) * u, wo.astype(cd))

        # ---- write results back into the mirrored recv layout ----
        flat = jnp.where(ekeep, jnp.where(valid, r_eid, 0) * C_e + epos,
                         E_loc * C_e)
        ye_pad = jnp.concatenate(
            [ye.reshape(E_loc * C_e, D),
             jnp.zeros((1, D), ye.dtype)], axis=0)
        back = jnp.take(ye_pad, flat, axis=0)              # (tp*C_send, D)

        ret = jax.lax.all_to_all(back, "model", 0, 0, tiled=True)

        # ---- combine ----
        ret_flat = jnp.concatenate(
            [ret, jnp.zeros((1, D), ret.dtype)], axis=0)
        a_idx = jnp.where(keep, dest * C_send + pos, tp * C_send)
        y_sel = jnp.take(ret_flat, a_idx, axis=0)          # (a_loc, D)
        w = (topw.reshape(a_loc, 1)
             * keep.reshape(a_loc, 1)).astype(y_sel.dtype)
        y = jnp.sum((y_sel * w).reshape(t_loc, K, D), axis=1)
        aux = jax.lax.pmean(aux, all_axes)                 # global mean
        return y.reshape(b_loc, S_loc, D).astype(x_loc.dtype), aux

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])
    return y, aux
