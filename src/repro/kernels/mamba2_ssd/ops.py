"""Dispatching wrapper for the Mamba2 SSD scan."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mamba2_ssd import ref


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def ssd(x, log_a, b, c, initial_state=None, *, impl: str = "ref",
        chunk: int = 128):
    if impl == "naive":
        return ref.ssd_naive(x, log_a, b, c, initial_state)
    if impl == "ref":
        return ref.ssd_chunked(x, log_a, b, c, initial_state, chunk=chunk)
    if impl == "kernel":
        from repro.kernels.mamba2_ssd import mamba2_ssd
        return mamba2_ssd.ssd_pallas(x, log_a, b, c, initial_state,
                                     chunk=chunk)
    raise ValueError(impl)


ssd_step = ref.ssd_step
