"""TPU Pallas grouped (per-expert) matmul for MoE FFNs.

Computes ye[e] = xe[e] @ w[e] for every expert e over capacity-grouped
token slots: xe (E, C, D) x w (E, D, F) -> (E, C, F).

Grid: (E, C/bc, F/bf, D/bd) with the contraction dimension minormost; a
f32 VMEM accumulator carries partial sums over the D tiles, so each output
tile is written to HBM once.  Tile defaults (bc, bf, bd) = (128, 128, 512)
are MXU-aligned; VMEM footprint = bc*bd + bd*bf (bf16) + bc*bf (f32)
~ 0.25 MB.  Empty slots (capacity padding) multiply zeros -- the dispatch
layer masks them, so no flag plumbing is needed here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _kernel(x_ref, w_ref, o_ref, acc_scr, *, nd: int):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]                                   # (bc, bd)
    w = w_ref[0]                                   # (bd, bf)
    acc_scr[...] += jax.lax.dot(
        x, w, preferred_element_type=jnp.float32)  # MXU f32 accumulate

    @pl.when(kd == nd - 1)
    def _final():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "block_d"))
def gmm(xe, w, *, block_c: int = 128, block_f: int = 128, block_d: int = 512):
    """xe (E, C, D) @ w (E, D, F) -> (E, C, F)."""
    E, C, D = xe.shape
    _, _, F = w.shape
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    assert C % bc == 0 and F % bf == 0 and D % bd == 0, (C, F, D)
    nd = D // bd

    kernel = functools.partial(_kernel, nd=nd)
    return pallas_call(
        kernel, xe, w,
        grid=(E, C // bc, F // bf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ic, jf, kd: (e, ic, kd)),
            pl.BlockSpec((1, bd, bf), lambda e, ic, jf, kd: (e, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda e, ic, jf, kd: (e, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
    )
