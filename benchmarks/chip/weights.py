"""Model weights made from ``--seed``, on the device, in the dtype they
are served in.

Each leaf is drawn from its own key: the seed's key folded with a hash
of the leaf's path and, for a layer's leaf, with the layer index.  The
served model makes every leaf in one jitted call; the reference makes
one layer at a time with the same keys and gets the same values, so it
never needs the program's arrays.  Norm scales are drawn around 1 (not
set to 1), so a norm whose scale is dropped changes the answer.  Which
leaves there are, their shapes and fan-ins, is the configuration's
family's (``families``, ``leaves``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import families

HALF = 1 << 15                  # integer grid of the uniform draws


def leaves(c: dict) -> dict:
    """path -> (shape of one layer's leaf, fan-in, layers): the family's."""
    return families.of(c).leaves(c)


def _group(path: str) -> str:
    """The group of a stacked leaf: its path's first two parts."""
    return "/".join(path.split("/")[:2])


def seed_key(seed: int):
    """A key from any whole number up to 62 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, fan_in, dtype):
    """Uniform values of standard deviation 1/sqrt(fan_in), or in
    [0.9, 1.1) for a norm.  Made of integers and one rounding step each,
    so every compilation of it gives the same bits."""
    i = jax.random.randint(key, shape, -HALF, HALF, jnp.int32)
    if fan_in is None:
        x = (i + 10 * HALF).astype(jnp.float32) * np.float32(0.1 / HALF)
    else:
        x = i.astype(jnp.float32) * np.float32(np.sqrt(3.0 / fan_in) / HALF)
    return x.astype(dtype)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _layer_key(key, path: str, layer):
    return jax.random.fold_in(_leaf_key(key, path), layer)


def global_leaf(key, c: dict, path: str, dtype=jnp.bfloat16):
    shape, fan_in, _ = leaves(c)[path]
    return _draw(_leaf_key(key, path), shape, fan_in, dtype)


def layer_leaf(key, c: dict, path: str, layer, dtype=jnp.bfloat16):
    shape, fan_in, _ = leaves(c)[path]
    return _draw(_layer_key(key, path, layer), shape, fan_in, dtype)


def all_leaves(key, c: dict, dtype=jnp.bfloat16) -> dict:
    """path -> array for the whole model; a stacked leaf stacked over
    its group's layers.  Meant to run inside one ``jax.jit``."""
    out = {}
    for p, (_, _, layers) in leaves(c).items():
        if layers:
            out[p] = jax.vmap(lambda l, p=p: layer_leaf(key, c, p, l, dtype))(
                jnp.arange(layers))
        else:
            out[p] = global_leaf(key, c, p, dtype)
    return out


def one_layer(key, c: dict, layer, dtype=jnp.bfloat16, group=None) -> dict:
    """Path below the group (``attn/wq``, ``ln1/scale``, ...) -> one
    layer's leaf of ``group``; without ``group``, of the only one."""
    stacked = [p for p, (_, _, layers) in leaves(c).items() if layers]
    if group is None:
        groups = {_group(p) for p in stacked}
        if len(groups) != 1:
            raise ValueError(f"name one of the groups {sorted(groups)}")
        group, = groups
    return {p.removeprefix(group + "/"): layer_leaf(key, c, p, layer, dtype)
            for p in stacked if _group(p) == group}


def as_tree(flat: dict) -> dict:
    """Nest ``a/b/c`` paths into dicts."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return tree


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out
