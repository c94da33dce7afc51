"""The decode step's KV-cache traffic: the grouped-query read of the
cache as stored, the in-place write of the new position into the
layer-stacked cache, and the engine's donation of the cache to each
step."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.distributed import axisenv
from repro.models import api
from repro.models.attention import NEG_INF, mha_reference
from repro.serving.engine import Engine

HI = jax.lax.Precision.HIGHEST


def _repeat_read(q, k, v, *, window, softcap, q_offset, valid_len):
    """The decode read as it was before the grouped one: K/V upcast and
    repeated to every query head, then one f32 pass over the cache."""
    B, Sq, H, hd = q.shape
    Sk, G = k.shape[1], H // k.shape[2]
    qf = q.astype(jnp.float32) * hd ** -0.5
    ke = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    ve = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bihd,bjhd->bhij", qf, ke, precision=HI)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < valid_len)
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", p, ve, precision=HI)


def _qkv(G, Sq, *, B=3, Sk=24, KVH=2, hd=16):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(G * 10 + Sq), 3)
    q = jax.random.normal(kq, (B, Sq, KVH * G, hd), jnp.float32)
    # the cache is stored in bf16; the read upcasts it
    k = jax.random.normal(kk, (B, Sk, KVH, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, Sk, KVH, hd), jnp.bfloat16)
    return q, k, v


@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("Sq,cur,window,softcap", [
    (1, 13, None, None),          # valid_len 14 of 24 positions
    (3, 20, 6, None),             # sliding window, three new positions
    (1, 23, None, 30.0),          # gemma2-style softcap, full cache
])
def test_grouped_decode_read_equals_the_repeat_read(G, Sq, cur, window,
                                                    softcap):
    q, k, v = _qkv(G, Sq)
    cur = jnp.asarray(cur, jnp.int32)
    got = jax.jit(lambda q, k, v, c: mha_reference(
        q, k, v, window=window, softcap=softcap, q_offset=c,
        valid_len=c + Sq))(q, k, v, cur)
    want = _repeat_read(q, k, v, window=window, softcap=softcap,
                        q_offset=cur, valid_len=cur + Sq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _shapes_in(text: str) -> set:
    """Every array shape a lowered program's types name."""
    return {tuple(map(int, m.split("x")))
            for m in re.findall(r"tensor<(\d+(?:x\d+)*)x[a-z]", text)}


def test_the_read_repeats_kv_only_where_a_mesh_shards_the_heads():
    q, k, v = _qkv(4, 1)
    B, Sk, H, hd = 3, 24, 8, 16

    def lowered():
        return jax.jit(lambda q, k, v: mha_reference(
            q, k, v, q_offset=jnp.int32(5),
            valid_len=jnp.int32(6))).lower(q, k, v).as_text()
    grouped = lowered()
    with axisenv.activation_axes(model="model", model_size=2):
        repeated = lowered()
    assert (B, Sk, H, hd) not in _shapes_in(grouped)
    assert (B, Sk, H, hd) in _shapes_in(repeated)


def _gqa4():
    """A small grouped-query config: 8 query heads over 2 KV heads."""
    return get_config("internlm2-1.8b", reduced=True).replace(
        num_heads=8, num_kv_heads=2)


def test_decode_program_writes_only_the_new_position():
    cfg = _gqa4()
    B, R, L = 4, 24, cfg.num_layers
    KVH, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    params = api.abstract_params(cfg)
    cache = jax.eval_shape(lambda: api.init_cache(cfg, B, R))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cur = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(lambda p, c, t, n: api.decode_step(p, cfg, c, t, n)
                   ).lower(params, cache, tok, cur).as_text()
    shapes = _shapes_in(text)
    # no group-repeated K/V, in either form jnp.repeat takes
    assert (B, R, H, hd) not in shapes
    assert (B, R, KVH, H // KVH, hd) not in shapes
    # one write per cache tensor, one position wide, into the stack
    updates = re.findall(r"stablehlo.dynamic_update_slice .*: \((.*)\) ->",
                         text)
    assert len(updates) == 2, updates
    for u in updates:
        whole, new = re.findall(r"tensor<([\dx]+)x\w+>", u)[:2]
        assert whole == f"{L}x{B}x{R}x{KVH}x{hd}"
        assert new == f"1x{B}x1x{KVH}x{hd}"


def test_engine_decode_donates_the_cache_unless_a_caller_holds_it():
    cfg = _gqa4()
    eng = Engine(cfg, api.init_params(cfg, jax.random.PRNGKey(0)),
                 max_new=4)
    prompts = np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=(4, 16), dtype=np.int32)
    ref = eng.generate(prompts, max_new=4)
    compiled = eng._decode.lower(
        eng.params, api.init_cache(cfg, 4, 20),
        jnp.zeros((4, 1), jnp.int32), jnp.int32(16)).compile().as_text()
    assert "input_output_alias" in compiled

    fed = []
    decode = eng._decode

    def spy(params, cache, *args):
        fed.append(cache)
        return decode(params, cache, *args)
    eng._decode = spy
    first, state = eng.prefill_batch(prompts, reserve=20)
    got = [first, eng.decode_batch(state)]
    # the engine's own cache is consumed by the step
    assert all(x.is_deleted() for x in jax.tree.leaves(fed[-1]))
    # a cache a caller reads stays whole: the step consumes a copy
    held = state.cache
    kept = [np.asarray(x) for x in jax.tree.leaves(held)]
    got.append(eng.decode_batch(state))
    assert not any(x.is_deleted() for x in jax.tree.leaves(held))
    for x, y in zip(jax.tree.leaves(held), kept):
        np.testing.assert_array_equal(np.asarray(x), y)
    got.append(eng.decode_batch(state))
    np.testing.assert_array_equal(np.stack(got, axis=1), ref[:, 16:])
