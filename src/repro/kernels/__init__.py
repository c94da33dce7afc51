"""Pallas TPU kernels, each beside a pure-jnp oracle (``ref.py``) and a
dispatcher (``ops.py``).

``pallas_call`` is the one place a kernel's execution mode is chosen:
Mosaic-compiled when the program is lowered for a TPU, interpreted on
every other platform (the CPU test backend).  The choice follows the
platform the program is lowered for, not the process's default backend,
so an ahead-of-time compile for a described TPU gets the real kernel.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *args, **kw):
    """``pl.pallas_call(kernel, **kw)(*args)``, never interpreted on a
    TPU and always interpreted elsewhere."""
    def run(interpret):
        return lambda *a: pl.pallas_call(kernel, interpret=interpret,
                                         **kw)(*a)
    return jax.lax.platform_dependent(*args, tpu=run(False),
                                      default=run(True))
