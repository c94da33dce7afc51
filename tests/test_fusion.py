"""Numerics-agreement regressions for the projections and dispatch paths
that GSPMD sharding constrains.

A concat (then split) across a TP-sharded dim once miscompiled under
GSPMD; fused paths stack along a *new leading axis* so shard boundaries
never move.  These tests pin that contract: fused and unfused K/V
projections must agree bit-tightly on the same inputs, the gated MLP (two
input einsums on the weights as passed, no stacked copy) must match a
float32 numpy reference, and the MoE dispatch/combine gathers (fill-mode
instead of pad-row concats along sharded dims) must keep matching the
one-hot einsum oracle even when capacity drops exercise the out-of-bounds
fill path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import api, moe
from repro.models.attention import attention_params, project_qkv
from repro.models.mlp import mlp, mlp_params


class _ParamMaker:
    """Deterministic dense param factory matching the mk.param call shape."""

    def __init__(self, seed=0):
        self.key = jax.random.PRNGKey(seed)

    def param(self, shape, axes, fan_in=None, init=None):
        self.key, sub = jax.random.split(self.key)
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        scale = 1.0 / np.sqrt(fan_in or shape[-1])
        return jax.random.normal(sub, shape, jnp.float32) * scale


def _gqa_cfg(**kw):
    cfg = get_config("qwen3-8b", reduced=True)
    return cfg.replace(compute_dtype="float32", param_dtype="float32", **kw)


def test_fused_kv_matches_unfused():
    cfg = _gqa_cfg(fuse_kv=True)
    assert cfg.num_kv_heads < cfg.num_heads      # exercise the GQA shapes
    params = attention_params(_ParamMaker(), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model),
                          jnp.float32)
    qf, kf, vf = project_qkv(params, x, cfg.replace(fuse_kv=True))
    qu, ku, vu = project_qkv(params, x, cfg.replace(fuse_kv=False))
    np.testing.assert_allclose(np.asarray(qf), np.asarray(qu))
    np.testing.assert_allclose(np.asarray(kf), np.asarray(ku),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vu),
                               rtol=1e-6, atol=1e-6)


_NP_ACT = {
    "silu": lambda z: z / (1.0 + np.exp(-z)),
    "gelu": lambda z: 0.5 * z * (1.0 + np.tanh(
        np.sqrt(2.0 / np.pi) * (z + 0.044715 * z ** 3))),
}


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_mlp_matches_float32_reference(act):
    cfg = _gqa_cfg(act=act)
    params = mlp_params(_ParamMaker(), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model),
                          jnp.float32)
    y = mlp(params, x, cfg)
    xn = np.asarray(x, np.float64)
    wg, wu, wo = (np.asarray(params[k], np.float64)
                  for k in ("wi_gate", "wi_up", "wo"))
    ref = (_NP_ACT[act](xn @ wg) * (xn @ wu)) @ wo
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


def _moe_setup(cf):
    cfg = get_config("kimi-k2-1t-a32b", reduced=True).replace(
        capacity_factor=cf, compute_dtype="float32", param_dtype="float32")
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    p = jax.tree.map(lambda t: t[0], params["stack"]["uniform"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_moe_fill_gather_matches_oracle_under_drops():
    """Tight capacity forces both the empty-slot fill (dispatch) and the
    dropped-assignment OOB fill (combine); the einsum oracle computes the
    same semantics with explicit one-hot masks."""
    cfg, p, x = _moe_setup(cf=0.5)
    y1, a1 = moe.moe_dropping(p, x, cfg)
    y2, a2 = moe.moe_einsum(p, x, cfg)
    assert bool(jnp.all(jnp.isfinite(y1)))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(a1) - float(a2)) < 1e-6
