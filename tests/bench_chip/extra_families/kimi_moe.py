"""A test-only configuration family: the repository's mixture-of-experts
decoder (``program_arch`` kimi-k2-1t-a32b), GQA attention as the dense
family's, and in every layer a softmax router over ``E`` experts whose
top ``K`` weights are normalised to sum to 1, each expert a gated FFN.

It shows that an architecture other than the dense decoder lands as a
family module and a configuration file: the tests register this
directory on ``benchmarks.chip.families.__path__`` and change no file
of the benchmark.  The program's tree is ``tok/*``, ``final_norm/scale``
and ``stack/uniform/{ln1,attn,ln2,ffn}``, with ``ffn/router`` (d, E) and
expert weights (E, d, f) stacked over ``L`` layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.families import dense
from benchmarks.chip.reference import F32, _mm, _norm

BYTES = 2                                   # bf16 weights and KV cache
GROUP = "stack/uniform/"


def dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
            "hd": c["head_dim"], "f": c["moe_intermediate_size"],
            "E": c["n_routed_experts"], "K": c["num_experts_per_tok"],
            "V": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"], "qk_norm": False,
            "act": c.get("hidden_act", "silu")}


def _layer_leaves(c: dict) -> dict:
    z = dims(c)
    d, H, KV, hd, f, E = z["d"], z["H"], z["KV"], z["hd"], z["f"], z["E"]
    return {"ln1/scale": ((d,), None),
            "attn/wq": ((d, H, hd), d),
            "attn/wk": ((d, KV, hd), d),
            "attn/wv": ((d, KV, hd), d),
            "attn/wo": ((H, hd, d), H * hd),
            "ln2/scale": ((d,), None),
            "ffn/router": ((d, E), d),
            "ffn/wi_gate": ((E, d, f), d),
            "ffn/wi_up": ((E, d, f), d),
            "ffn/wo": ((E, f, d), f)}


def leaves(c: dict) -> dict:
    z = dims(c)
    out = {"tok/embed": ((z["V"], z["d"]), 1, 0),
           "final_norm/scale": ((z["d"],), None, 0),
           "tok/unembed": ((z["d"], z["V"]), z["d"], 0)}
    out.update({GROUP + p: (s, f, z["L"])
                for p, (s, f) in _layer_leaves(c).items()})
    return out


def program_config(c: dict):
    """The program's MoE config at the file's sizes.  Its dispatch gives
    each expert ``capacity_factor * K / E`` slots per token of a row;
    at ``E / K`` that is one slot per token, so an expert can take every
    token of the row and none is dropped: the served model is the
    reference's."""
    from repro.configs.base import get_config
    z = dims(c)
    return get_config(c["program_arch"]).replace(
        num_layers=z["L"], d_model=z["d"], num_heads=z["H"],
        num_kv_heads=z["KV"], head_dim=z["hd"], d_ff=z["f"],
        num_experts=z["E"], num_experts_per_token=z["K"],
        capacity_factor=z["E"] / z["K"], moe_impl="dropping",
        vocab_size=z["V"], norm_eps=z["eps"], rope_theta=z["theta"],
        qk_norm=False, act=z["act"],
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        param_dtype=c["dtype"], compute_dtype=c["dtype"])


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _layer(x, w, *, z, quant):
    """One MoE decoder layer over (N, T, d) float32: every expert runs
    over every token, and the router's top-K weights mix them."""
    w = {k: v.astype(F32) for k, v in w.items()}
    x = dense.attention(x, w, z, quant)
    m = _norm(x, w["ln2/scale"], z["eps"])
    gates = jax.nn.softmax(_mm(m, w["ffn/router"], quant), axis=-1)
    topw, topi = jax.lax.top_k(gates, z["K"])
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    mix = jnp.sum(jax.nn.one_hot(topi, z["E"]) * topw[..., None], axis=-2)
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[z["act"]]

    def expert(wg, wu, wo):
        return _mm(act(_mm(m, wg, quant)) * _mm(m, wu, quant), wo, quant)

    ye = jax.vmap(expert)(w["ffn/wi_gate"], w["ffn/wi_up"], w["ffn/wo"])
    return x + jnp.einsum("entd,nte->ntd", ye, mix)


def logits(c: dict, seed: int, tokens: np.ndarray, positions: np.ndarray,
           quant=None) -> np.ndarray:
    return dense.run_layers(c, seed, tokens, positions, quant, dims(c),
                            _layer)


# -- operations and bytes: the K experts a token is routed to count as
# computed, and every expert's weights as read --------------------------------

def _numel(shape) -> int:
    return int(np.prod(shape))


def _active_matmul_params(c: dict) -> int:
    z = dims(c)
    n = 0
    for p, (shape, fan_in) in _layer_leaves(c).items():
        if fan_in is not None:
            share = z["K"] / z["E"] if p.startswith("ffn/w") else 1
            n += int(_numel(shape) * share)
    return n


def _params(c: dict) -> int:
    """Every weight but the embedding table."""
    return sum(_numel(s) * max(layers, 1)
               for p, (s, _, layers) in leaves(c).items() if p != "tok/embed")


def _kv_bytes(c: dict, rows: int, positions: int) -> float:
    z = dims(c)
    return 2.0 * z["L"] * rows * positions * z["KV"] * z["hd"] * BYTES


def prefill_flops(c: dict, B: int, S: int) -> float:
    z = dims(c)
    return (2.0 * B * S * z["L"] * _active_matmul_params(c)
            + 4.0 * B * z["L"] * z["H"] * z["hd"] * S * (S + 1) / 2
            + 2.0 * B * z["d"] * z["V"])


def decode_flops(c: dict, B: int, pos: int) -> float:
    z = dims(c)
    return (2.0 * B * z["L"] * _active_matmul_params(c)
            + 4.0 * B * z["L"] * z["H"] * z["hd"] * (pos + 1)
            + 2.0 * B * z["d"] * z["V"])


def weight_bytes(c: dict, rows: int) -> float:
    return (_params(c) + rows * dims(c)["d"]) * BYTES


def prefill_bytes(c: dict, B: int, S: int) -> float:
    return weight_bytes(c, B * S) + _kv_bytes(c, B, S)


def decode_bytes(c: dict, B: int, pos: int) -> float:
    return weight_bytes(c, B) + _kv_bytes(c, B, pos + 1)
