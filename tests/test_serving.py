"""Serving engine behaviour."""
import jax
import numpy as np

from repro.configs.base import get_config
from repro.models import api
from repro.serving.engine import Engine


def test_generate_shapes_and_determinism():
    cfg = get_config("internlm2-1.8b", reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_new=6)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(3, 16), dtype=np.int32)
    out1 = eng.generate(prompts)
    out2 = eng.generate(prompts)
    assert out1.shape == (3, 22)
    np.testing.assert_array_equal(out1, out2)   # greedy = deterministic
    np.testing.assert_array_equal(out1[:, :16], prompts)


def test_generate_matches_full_forward_argmax():
    """Greedy decode via the KV cache equals argmax over repeated full
    forward passes (incremental == recomputed)."""
    import jax.numpy as jnp
    cfg = get_config("qwen3-8b", reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_new=4)
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 12), dtype=np.int32)
    out = eng.generate(prompts)

    toks = jnp.asarray(prompts)
    for _ in range(4):
        logits, _ = api.forward(params, cfg, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, np.asarray(toks))


def test_engine_ssm_arch():
    cfg = get_config("rwkv6-3b", reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_new=4)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 8), dtype=np.int32)
    out = eng.generate(prompts)
    assert out.shape == (2, 12)
    # a second call reuses the compiled bucket and is deterministic
    np.testing.assert_array_equal(eng.generate(prompts), out)


def test_engine_stepwise_matches_generate():
    """The shard's stepwise prefill/decode path emits exactly the tokens
    ``generate`` would, and ``gather_rows`` keeps the surviving rows'
    continuations identical after a slot-reuse compaction.  Each step
    consumes the cache it is given (donated), the compaction's gather
    too, on a grouped-query config (8 query heads over 2 KV heads); the
    tokens are also those of repeated full forward passes."""
    import jax.numpy as jnp
    cfg = get_config("internlm2-1.8b", reduced=True).replace(
        num_heads=8, num_kv_heads=2)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_new=6)
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=(4, 16), dtype=np.int32)
    ref = eng.generate(prompts, max_new=6)

    first, state = eng.prefill_batch(prompts, reserve=16 + 6)
    got = [first]
    for _ in range(2):
        got.append(eng.decode_batch(state))
    # retire rows 1 and 3 mid-generation; survivors keep decoding
    state = eng.gather_rows(state, [0, 2])
    tail = [eng.decode_batch(state) for _ in range(3)]
    full = np.stack(got, axis=1)
    np.testing.assert_array_equal(full, ref[:, 16:16 + 3])
    np.testing.assert_array_equal(np.stack(tail, axis=1),
                                  ref[[0, 2], 16 + 3:])

    toks = jnp.asarray(prompts)
    for _ in range(6):
        logits, _ = api.forward(params, cfg, {"tokens": toks})
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(ref, np.asarray(toks))
