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
import math
import os
import re

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


def _internlm2_prefill(sharding, B=32, S=128):
    """The full-width internlm2-1.8b prefill of a (B, S) batch:
    (config, lowered program)."""
    cfg = get_config("internlm2-1.8b")
    params = _shapes(api.abstract_params(cfg), sharding)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sharding)
    prefill = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t}))
    return cfg, prefill.lower(params, tokens)


def test_internlm2_full_width_prefill_compiles(one_chip):
    _, lowered = _internlm2_prefill(one_chip, B=8)
    assert _memory_bytes(lowered.compile()) < V5E_HBM_BYTES


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


_NO_WRITE = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
             "conditional", "call"}


def _writes(hlo: str) -> list:
    """(opcode, dtype, shape, name of the called computation) of every
    array an optimized module writes to memory: the outputs of the
    instructions outside fusion bodies."""
    out, comp, fused = [], None, False
    for line in hlo.splitlines():
        if not line.startswith(" "):
            comp = line.split(" ")[0] if line.strip() else None
            fused = comp is not None and "fused_computation" in comp
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w\-]+)\(", line)
        if fused or not m or m.group(2) in _NO_WRITE:
            continue
        calls = re.search(r"calls=%([\w.\-]+)", line)
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append((m.group(2), dtype, shape, calls and calls.group(1)))
    return out


def _root_op(hlo: str, computation: str) -> str:
    body = hlo[hlo.index(f"\n%{computation} "):]
    root = re.search(r"ROOT %\S+ = .*? ([\w\-]+)\(", body)
    return root.group(1)


QWEN3_B, QWEN3_R = 32, 192               # the served batch and cache


def _qwen3_decode_step(sharding):
    """The served decode step at qwen3-8b's widths, 2 layers, cache
    donated: (config, lowered program)."""
    cfg = get_config("qwen3-8b").replace(num_layers=2)
    params = _shapes(api.abstract_params(cfg), sharding)
    cache = _shapes(
        jax.eval_shape(lambda: api.init_cache(cfg, QWEN3_B, QWEN3_R)),
        sharding)
    tok = jax.ShapeDtypeStruct((QWEN3_B, 1), jnp.int32, sharding=sharding)
    cur = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    decode = jax.jit(lambda p, c, t, n: api.decode_step(p, cfg, c, t, n),
                     donate_argnums=1)
    return cfg, decode.lower(params, cache, tok, cur)


def test_qwen3_decode_step_reads_the_kv_cache_in_place(one_chip):
    """The served decode step at qwen3-8b's widths and the served cache
    geometry (B=32, reserve 192), compiled for the chip: the cache is
    aliased to the step's output, and per layer no op writes an f32 or
    group-repeated copy of the layer's K/V, and the only ops that write
    the layer-stacked cache are in-place updates of the new position."""
    cfg, lowered = _qwen3_decode_step(one_chip)
    B, R = QWEN3_B, QWEN3_R
    KVH, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    hlo = lowered.compile().as_text()
    assert "input_output_alias" in hlo
    layer, stack = B * R * KVH * hd, cfg.num_layers * B * R * KVH * hd
    writes = [(op, dt, math.prod(shape), calls)
              for op, dt, shape, calls in _writes(hlo)]
    assert not [w for w in writes if w[1] == "f32" and w[2] == layer]
    assert not [w for w in writes if w[2] == B * R * H * hd]
    stacked = [w for w in writes if w[2] == stack]
    assert len(stacked) == 2, stacked
    assert all(w[0] == "fusion"
               and _root_op(hlo, w[3]) == "dynamic-update-slice"
               for w in stacked), stacked


@pytest.mark.parametrize("program", [_qwen3_decode_step, _internlm2_prefill],
                         ids=["qwen3-decode", "internlm2-prefill"])
def test_ffn_weights_are_read_in_place(one_chip, program):
    """The gated MLP's matmuls read each layer's gate and up weights out
    of the layer-stacked params where they lie: no op writes an array of
    one layer's (d_model, d_ff) weight shape, alone or stacked in pairs.
    Shapes, not element counts: the internlm2 prefill's [32,128,8192]
    activation holds exactly 2 x d_model x d_ff elements."""
    cfg, lowered = program(one_chip)
    d, f = cfg.d_model, cfg.d_ff
    weight = {(d, f), (1, d, f), (2, d, f)}
    copies = [w for w in _writes(lowered.compile().as_text())
              if w[2] in weight]
    assert not copies, copies
