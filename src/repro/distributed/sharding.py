"""Logical-axis -> mesh-axis sharding rules (DP/TP/EP/FSDP/ZeRO).

Every parameter is annotated once with logical axis names by the model's
``params(mk, cfg)`` function (SpecMaker).  This module resolves those names
to concrete ``PartitionSpec``s for a given mesh + mode:

- ``dp_tp``   : params replicated over (pod, data); tensor-parallel axes
                (vocab/ff/heads/experts/ssm channels) sharded over "model".
- ``fsdp_tp`` : dp_tp + the largest remaining unsharded axis of each big
                param additionally sharded over "data" (ZeRO-3 / FSDP).

Divisibility is checked per-tensor: an axis whose size does not divide the
mesh axis falls back to replication (e.g. granite's single KV head).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

# logical axis -> preferred mesh axis (dp_tp mode)
TP_RULES = {
    "vocab": "model",
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    # everything else (embed, embed2, head_dim, layer, conv, state, lora,
    # ...) -> replicated
}

# axes eligible for the extra FSDP ("data") shard, in priority order
FSDP_AXES = ("embed", "embed2", "ff", "head_dim", "vocab", "experts")

# parameters smaller than this stay replicated in fsdp mode (norm scales,
# biases -- sharding them only adds collective launches)
FSDP_MIN_SIZE = 1 << 16


def spec_for(axes, shape, mesh: Mesh, mode: str = "dp_tp") -> P:
    """Resolve one parameter's logical axes to a PartitionSpec.

    Modes: dp_tp (TP over "model"), fsdp_tp (dp_tp + FSDP over "data"),
    dp_only (no TP -- params replicated, every mesh axis is data parallel;
    the right choice for models far smaller than the pod)."""
    assert len(axes) == len(shape), (axes, shape)
    used = set()
    out = [None] * len(axes)
    # pass 1: tensor-parallel assignment
    if mode != "dp_only":
        for i, (name, dim) in enumerate(zip(axes, shape)):
            m = TP_RULES.get(name)
            if m and m in mesh.shape and m not in used \
                    and dim % mesh.shape[m] == 0:
                out[i] = m
                used.add(m)
    # pass 2: FSDP extra shard over "data"
    if mode == "fsdp_tp" and "data" in mesh.shape and \
            int(np.prod(shape)) >= FSDP_MIN_SIZE:
        for pref in FSDP_AXES:
            done = False
            for i, (name, dim) in enumerate(zip(axes, shape)):
                if name == pref and out[i] is None and \
                        dim % mesh.shape["data"] == 0 and "data" not in used:
                    out[i] = "data"
                    used.add("data")
                    done = True
                    break
            if done:
                break
    return P(*out)


def tree_specs(spec_tree, shape_tree, mesh: Mesh, mode: str = "dp_tp"):
    """Map spec_for over a (logical-axes tree, ShapeDtypeStruct tree)."""
    return jax.tree.map(
        lambda axes, s: spec_for(axes, s.shape, mesh, mode),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


# ---------------------------------------------------------------------------
# Batch / activation sharding
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh, global_batch: int, mode: str = "dp_tp"):
    """Greedy batch partitioning over (pod, data) -- plus "model" in
    dp_only mode, where the whole pod is data-parallel."""
    names = ("pod", "data", "model") if mode == "dp_only" \
        else ("pod", "data")
    axes = []
    rem = global_batch
    for ax in names:
        if ax in mesh.shape and rem % mesh.shape[ax] == 0 and mesh.shape[ax] > 1:
            axes.append(ax)
            rem //= mesh.shape[ax]
    return tuple(axes)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state sharding
# ---------------------------------------------------------------------------


def zero_spec(param_spec: P, shape, mesh: Mesh) -> P:
    """Shard optimizer moments over "data" on the first free divisible dim
    (ZeRO-1).  Keeps the param's own spec for the other dims."""
    if "data" not in mesh.shape or int(np.prod(shape)) < FSDP_MIN_SIZE:
        return param_spec
    spec = list(param_spec) + [None] * (len(shape) - len(param_spec))
    if "data" in spec or ("pod", "data") in spec:
        return param_spec
    for i, (cur, dim) in enumerate(zip(spec, shape)):
        if cur is None and dim % mesh.shape["data"] == 0:
            spec[i] = "data"
            return P(*spec)
    return param_spec
