"""Jitted wrapper / dispatcher for flash attention."""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.flash_attention import flash_attention  # noqa: F401

attention_reference = ref.attention_reference


def attention(q, k, v, *, impl: str = "kernel", **kw):
    if impl == "kernel":
        return flash_attention(q, k, v, **kw)
    return ref.attention_reference(q, k, v, **{
        k_: v_ for k_, v_ in kw.items()
        if k_ in ("causal", "window", "softcap", "q_offset")})
