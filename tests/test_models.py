"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, asserting output shapes + finiteness; plus incremental-decoding
consistency (prefill + decode_step == full forward)."""
import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.launch import steps
from repro.models import api

B, S = 2, 32


def _batch(cfg, key):
    ks = jax.random.split(key, 4)
    batch = {}
    if cfg.family == "vlm":
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model))
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
    else:
        batch["tokens"] = jax.random.randint(ks[1], (B, S), 0,
                                             cfg.vocab_size)
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(ks[2], (B, S, cfg.d_model))
    batch["labels"] = jax.random.randint(ks[3], (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = api.forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    loss, metrics = api.loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    from repro.configs.base import ShardingConfig, TrainConfig
    cfg = get_config(arch, reduced=True)
    state = steps.init_state(cfg, jax.random.PRNGKey(0))
    # warmup_steps=0: the linear warmup gives lr=0 at step 0, which would
    # (correctly) leave parameters unchanged on the very first step
    fn = steps.make_train_step(cfg, TrainConfig(lr=1e-3, warmup_steps=0,
                                                total_steps=10),
                               ShardingConfig())
    batch = _batch(cfg, jax.random.PRNGKey(1))
    new_state, metrics = jax.jit(fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["skipped"]) == 0.0
    assert int(new_state["opt"].step) == 1
    # parameters actually moved
    delta = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_incremental_decode_matches_forward(arch):
    cfg = get_config(arch, reduced=True)
    if cfg.family == "vlm":
        pytest.skip("vlm backbone takes embeds; decode exercised via tokens")
    if cfg.is_moe:
        # capacity-based MoE is sequence-dependent: in a full forward pass
        # tokens compete for expert capacity, while a decoded token is
        # routed alone.  With enough capacity (no drops) the two paths are
        # token-independent and must agree exactly.
        cfg = cfg.replace(capacity_factor=8.0)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    batch_full = {"tokens": tokens}
    batch_prefix = {"tokens": tokens[:, :S]}
    if cfg.is_encdec:
        frames = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
        batch_full["frames"] = frames
        batch_prefix["frames"] = frames

    logits_full, _ = api.forward(params, cfg, batch_full)
    pf_logits, cache = api.prefill(params, cfg, batch_prefix)

    # prefill's last-position logits == forward at position S-1
    np.testing.assert_allclose(
        np.asarray(pf_logits, np.float32),
        np.asarray(logits_full[:, S - 1], np.float32),
        rtol=0.05, atol=0.05)

    # one decode step == forward at position S
    cache = api.grow_cache(cfg, cache, S + 1)
    dl, _ = api.decode_step(params, cfg, cache, tokens[:, S:S + 1],
                            jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(dl, np.float32),
        np.asarray(logits_full[:, S], np.float32),
        rtol=0.05, atol=0.05)


def test_gemma2_softcap_bounds_logits():
    cfg = get_config("gemma2-2b", reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, _ = api.forward(params, cfg, batch)
    assert float(jnp.max(jnp.abs(logits))) <= cfg.final_logit_softcap + 1e-3


def test_param_count_analytic_close():
    """Analytic param accounting tracks actual trees within 5%."""
    from repro.configs.base import param_count
    from repro.utils.trees import tree_count_params
    for arch in ("internlm2-1.8b", "qwen3-8b", "kimi-k2-1t-a32b",
                 "rwkv6-3b", "seamless-m4t-medium"):
        cfg = get_config(arch, reduced=True)
        actual = tree_count_params(api.abstract_params(cfg))
        predicted = param_count(cfg)
        assert abs(actual - predicted) / actual < 0.05, \
            (arch, actual, predicted)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 0.05)])
def test_prefill_matches_float32_reference(dtype, tol):
    """The served prefill's last-position logits against the plain
    float32 reference forward: to rounding in float32, and within the
    bound chip_smoke.py holds the full-width bf16 model to (as a share
    of the largest reference logit)."""
    from repro.models import reference
    cfg = get_config("internlm2-1.8b", reduced=True).replace(
        param_dtype=dtype, compute_dtype=dtype)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    logits, _ = api.prefill(params, cfg, {"tokens": toks})
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.last_logits(params, cfg, toks))
    err = np.max(np.abs(np.asarray(logits, np.float32) - ref))
    assert err <= tol * np.max(np.abs(ref)), err


def test_every_config_field_is_read():
    """Every field of the model, sharding and train configs is read as an
    attribute somewhere under src/repro: an option nothing reads is dead
    weight every new model threads through."""
    from repro.configs.base import ModelConfig, ShardingConfig, TrainConfig
    root = pathlib.Path(api.__file__).resolve().parents[1]
    read = {node.attr for path in root.rglob("*.py")
            for node in ast.walk(ast.parse(path.read_text()))
            if isinstance(node, ast.Attribute)
            and isinstance(node.ctx, ast.Load)}
    unread = [f"{c.__name__}.{f.name}"
              for c in (ModelConfig, ShardingConfig, TrainConfig)
              for f in dataclasses.fields(c) if f.name not in read]
    assert not unread, unread
