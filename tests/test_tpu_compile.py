"""Ahead-of-time compiles for a described TPU v5e: the main path's
kernels and the full-width internlm2-1.8b serving programs, at real
sizes, through the TPU compiler and with no chip attached.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  These tests stay in this one file for the same
reason.  Nothing runs, so they say nothing about results or times; they
catch what the chip's compiler refuses (kernel layouts interpret mode
accepts, programs that do not fit the device).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.mpnn_mp.mpnn_mp import message_pass_pallas
from repro.models import api

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 - any describe failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_flash_attention_compiles_to_mosaic(one_chip):
    q = jax.ShapeDtypeStruct((8, 512, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 512, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mpnn_message_pass_compiles_to_mosaic(one_chip, dtype):
    B, N, Hd = 64, 16, 64                 # the surrogate's width
    h, e, adj = _shapes((jax.ShapeDtypeStruct((B, N, Hd), dtype),
                         jax.ShapeDtypeStruct((B, N, N, Hd, Hd), dtype),
                         jax.ShapeDtypeStruct((B, N, N), dtype)), one_chip)
    compiled = message_pass_pallas.lower(h, e, adj).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _memory_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_internlm2_full_width_prefill_compiles(one_chip):
    cfg = get_config("internlm2-1.8b")
    params = _shapes(api.abstract_params(cfg), one_chip)
    tokens = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=one_chip)
    prefill = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t}))
    compiled = prefill.lower(params, tokens).compile()
    assert _memory_bytes(compiled) < V5E_HBM_BYTES


def test_internlm2_full_width_decode_step_fits_one_chip(one_chip):
    cfg = get_config("internlm2-1.8b")
    B, reserve = 8, 144                   # the smoke's batch and cache
    params = _shapes(api.abstract_params(cfg), one_chip)
    cache = _shapes(jax.eval_shape(lambda: api.init_cache(cfg, B, reserve)),
                    one_chip)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    cur = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    decode = jax.jit(lambda p, c, t, n: api.decode_step(p, cfg, c, t, n))
    compiled = decode.lower(params, cache, tok, cur).compile()
    assert _memory_bytes(compiled) < V5E_HBM_BYTES
