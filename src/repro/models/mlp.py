"""Dense gated MLP (SwiGLU / GeGLU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import axisenv


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def mlp_params(mk, cfg: ModelConfig, stacked=(), d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    lead = tuple("layer" for _ in stacked)
    return {
        "wi_gate": mk.param(stacked + (d, f), lead + ("embed", "ff"), fan_in=d),
        "wi_up": mk.param(stacked + (d, f), lead + ("embed", "ff"), fan_in=d),
        "wo": mk.param(stacked + (f, d), lead + ("ff", "embed"), fan_in=f),
    }


def mlp(params, x, cfg: ModelConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    act = _act(cfg.act)
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(cd))
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(cd))
    h = axisenv.constrain(act(g) * u, "batch", None, "model")
    out = jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(cd))
    return axisenv.constrain(out, "batch", None, None)
