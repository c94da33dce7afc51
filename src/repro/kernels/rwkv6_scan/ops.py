"""Dispatching wrapper for the RWKV6 WKV scan."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rwkv6_scan import ref


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def wkv6(r, k, v, log_w, u, initial_state=None, *, impl: str = "ref",
         chunk: int = 64):
    if impl == "naive":
        return ref.wkv6_naive(r, k, v, log_w, u, initial_state)
    if impl == "ref":
        return ref.wkv6_chunked(r, k, v, log_w, u, initial_state,
                                chunk=chunk)
    if impl == "kernel":
        from repro.kernels.rwkv6_scan import rwkv6_scan
        return rwkv6_scan.wkv6_pallas(r, k, v, log_w, u, initial_state,
                                      chunk=chunk)
    raise ValueError(impl)


wkv6_step = ref.wkv6_step
