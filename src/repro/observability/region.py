"""Named regions on the profiler's clock.

``obs.region(name, **args)`` marks a phase of the inference shard's
serve loop or of the engine's calls.  While a ``jax.profiler`` session
is active in the process it is a ``jax.profiler.TraceAnnotation``: the
region lands on the host timeline of the same trace as the device's
operations, so device-idle time can be charged to what the host was
doing.  Otherwise it returns one shared no-op context and formats no
args.

It follows the profiler session and nothing else: no flag, no
environment variable.  It never imports jax; it uses jax only where the
process has already imported it, so fabric processes that import the
shard module stay off the accelerator stack.  Names are declared in
``observability.names.REGION_NAMES``.
"""
from __future__ import annotations

import sys
from contextlib import nullcontext

_OFF = nullcontext()


def region(name: str, **args):
    """A ``TraceAnnotation`` named ``name`` with ``args`` as its stats
    while a profiler session is active; else the shared no-op."""
    annotation = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                         None)
    if annotation is None or not annotation.is_enabled():
        return _OFF
    return annotation(name, **args)
