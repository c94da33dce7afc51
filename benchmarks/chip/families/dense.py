"""The dense decoder family: GQA attention with RoPE, an optional
per-head q/k RMS norm (Qwen3), and a gated FFN in every layer, one
uniform stack (InternLM2, Qwen3).

The program's tree is ``tok/*``, ``final_norm/scale`` and
``stack/uniform/{ln1,attn,ln2,ffn}`` stacked over all ``L`` layers.  The
reference runs one layer at a time (a whole Qwen3-8B stage in float32
would not fit a 16 GB chip), with each layer's weights made from the
seed in the dtype they are served in and cast to float32.  The counts
take every FFN weight of every layer as computed and read each step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W
from benchmarks.chip.reference import F32, _mm, _norm, _rope

BYTES = 2                                   # bf16 weights and KV cache


def dims(c: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    return {"d": c["hidden_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "hd": c["head_dim"], "f": c["intermediate_size"],
            "V": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"], "qk_norm": bool(c.get("qk_norm")),
            "act": c.get("hidden_act", "silu")}


def layer_leaves(c: dict) -> dict:
    """path -> (shape of one layer's leaf, fan-in or None for a norm)."""
    z = dims(c)
    d, H, KV, hd, f = z["d"], z["H"], z["KV"], z["hd"], z["f"]
    out = {"stack/uniform/ln1/scale": ((d,), None),
           "stack/uniform/attn/wq": ((d, H, hd), d),
           "stack/uniform/attn/wk": ((d, KV, hd), d),
           "stack/uniform/attn/wv": ((d, KV, hd), d),
           "stack/uniform/attn/wo": ((H, hd, d), H * hd),
           "stack/uniform/ln2/scale": ((d,), None),
           "stack/uniform/ffn/wi_gate": ((d, f), d),
           "stack/uniform/ffn/wi_up": ((d, f), d),
           "stack/uniform/ffn/wo": ((f, d), f)}
    if z["qk_norm"]:
        out["stack/uniform/attn/q_norm"] = ((hd,), None)
        out["stack/uniform/attn/k_norm"] = ((hd,), None)
    return out


def global_leaves(c: dict) -> dict:
    """path -> (shape, fan-in; 1 for the embedding table, None for a norm)."""
    z = dims(c)
    out = {"tok/embed": ((z["V"], z["d"]), 1),
           "final_norm/scale": ((z["d"],), None)}
    if not c.get("tie_word_embeddings"):
        out["tok/unembed"] = ((z["d"], z["V"]), z["d"])
    return out


def leaves(c: dict) -> dict:
    L = dims(c)["L"]
    out = {p: (s, f, 0) for p, (s, f) in global_leaves(c).items()}
    out.update({p: (s, f, L) for p, (s, f) in layer_leaves(c).items()})
    return out


def program_config(c: dict):
    """The program's config for the configuration file's architecture,
    with every size as the file states it."""
    from repro.configs.base import get_config
    z = dims(c)
    return get_config(c["program_arch"]).replace(
        num_layers=z["L"], d_model=z["d"], num_heads=z["H"],
        num_kv_heads=z["KV"], head_dim=z["hd"], d_ff=z["f"],
        vocab_size=z["V"], norm_eps=z["eps"], rope_theta=z["theta"],
        qk_norm=z["qk_norm"], act=z["act"],
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        param_dtype=c["dtype"], compute_dtype=c["dtype"])


# -- the float32 reference ---------------------------------------------------

def attention(x, w, z, quant):
    """The attention half of a layer with its residual, over (N, T, d)
    float32 with float32 weights ``w``; query head h reads KV head
    h // (H // KV)."""
    N, T, d = x.shape
    H, KV, hd, eps = z["H"], z["KV"], z["hd"], z["eps"]
    inv = 1.0 / z["theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    a = _norm(x, w["ln1/scale"], eps)
    q = _mm(a, w["attn/wq"].reshape(d, H * hd), quant).reshape(N, T, H, hd)
    k = _mm(a, w["attn/wk"].reshape(d, KV * hd), quant).reshape(N, T, KV, hd)
    v = _mm(a, w["attn/wv"].reshape(d, KV * hd), quant).reshape(N, T, KV, hd)
    if z["qk_norm"]:
        q = _norm(q, w["attn/q_norm"], eps)
        k = _norm(k, w["attn/k_norm"], eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nqhk,nshk->nhqs", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqs,nshk->nqhk", p, v).reshape(N, T, H * hd)
    return x + _mm(o, w["attn/wo"].reshape(H * hd, d), quant)


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _layer(x, w, *, z, quant):
    """One decoder layer over (N, T, d) float32."""
    w = {k: v.astype(F32) for k, v in w.items()}
    x = attention(x, w, z, quant)
    m = _norm(x, w["ln2/scale"], z["eps"])
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[z["act"]]
    g = act(_mm(m, w["ffn/wi_gate"], quant)) * _mm(m, w["ffn/wi_up"], quant)
    return x + _mm(g, w["ffn/wo"], quant)


@functools.partial(jax.jit, static_argnames=("c_items",))
def _layer_weights(key, layer, *, c_items):
    """One layer's leaves, below its group, in the served dtype."""
    c = dict(c_items)
    return W.one_layer(key, c, layer, jnp.dtype(c["dtype"]))


@functools.partial(jax.jit, static_argnames=("c_items", "path"))
def _global(key, *, c_items, path):
    c = dict(c_items)
    return W.global_leaf(key, c, path, jnp.dtype(c["dtype"]))


def _hashable(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(h, scale, unembed, *, eps, quant):
    h = _norm(h, scale.astype(F32), eps)
    return _mm(h, unembed.astype(F32), quant)


def run_layers(c: dict, seed: int, tokens, positions, quant, z: dict,
               layer) -> np.ndarray:
    """Embedding, ``layer(x, w, z=..., quant=...)`` over each of the
    ``z["L"]`` layers with its weights, and the head at ``positions``."""
    ci = _hashable(c)
    key = W.seed_key(seed)
    zs = tuple(sorted(z.items()))
    with jax.default_matmul_precision("highest"):
        emb = _global(key, c_items=ci, path="tok/embed")
        x = jnp.take(emb, jnp.asarray(tokens), axis=0).astype(F32)
        del emb
        for i in range(z["L"]):
            w = _layer_weights(key, jnp.int32(i), c_items=ci)
            x = layer(x, w, z=_Static(zs), quant=quant)
        idx = jnp.asarray(positions)
        h = jnp.take_along_axis(x, idx[..., None], axis=1)      # (N, P, d)
        del x
        unembed_path = ("tok/embed" if c.get("tie_word_embeddings")
                        else "tok/unembed")
        un = _global(key, c_items=ci, path=unembed_path)
        if c.get("tie_word_embeddings"):
            un = un.T
        scale = _global(key, c_items=ci, path="final_norm/scale")
        out = _head(h, scale, un, eps=float(z["eps"]), quant=quant)
        return np.asarray(out)


def logits(c: dict, seed: int, tokens: np.ndarray, positions: np.ndarray,
           quant=None) -> np.ndarray:
    return run_layers(c, seed, tokens, positions, quant, dims(c), _layer)


class _Static(dict):
    """The sizes as a hashable static argument that reads like a dict."""

    def __init__(self, items):
        super().__init__(items)
        self._items = tuple(items)

    def __hash__(self):
        return hash(self._items)

    def __eq__(self, other):
        return isinstance(other, _Static) and self._items == other._items


# -- operations and bytes ----------------------------------------------------

def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def _layer_matmul_params(c: dict) -> int:
    return sum(_numel(shape) for shape, fan_in in layer_leaves(c).values()
               if fan_in is not None)


def _layer_params(c: dict) -> int:
    return sum(_numel(shape) for shape, _ in layer_leaves(c).values())


def _head_params(c: dict) -> int:
    """The final norm and the unembedding (the table itself when tied)."""
    z = dims(c)
    return z["d"] + z["d"] * z["V"]


def prefill_flops(c: dict, B: int, S: int) -> float:
    z = dims(c)
    linear = 2.0 * B * S * z["L"] * _layer_matmul_params(c)
    attn = 4.0 * B * z["L"] * z["H"] * z["hd"] * S * (S + 1) / 2
    head = 2.0 * B * z["d"] * z["V"]
    return linear + attn + head


def decode_flops(c: dict, B: int, pos: int) -> float:
    z = dims(c)
    linear = 2.0 * B * z["L"] * _layer_matmul_params(c)
    attn = 4.0 * B * z["L"] * z["H"] * z["hd"] * (pos + 1)
    head = 2.0 * B * z["d"] * z["V"]
    return linear + attn + head


def _kv_bytes(c: dict, rows: int, positions: int) -> float:
    z = dims(c)
    return 2.0 * z["L"] * rows * positions * z["KV"] * z["hd"] * BYTES


def weight_bytes(c: dict, rows: int) -> float:
    """Weights one call reads: all but the embedding table, plus the
    table's ``rows`` rows."""
    z = dims(c)
    return (z["L"] * _layer_params(c) + _head_params(c)
            + rows * z["d"]) * BYTES


def prefill_bytes(c: dict, B: int, S: int) -> float:
    return weight_bytes(c, B * S) + _kv_bytes(c, B, S)


def decode_bytes(c: dict, B: int, pos: int) -> float:
    return weight_bytes(c, B) + _kv_bytes(c, B, pos + 1)
