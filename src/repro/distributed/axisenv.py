"""Ambient activation-sharding environment.

Model code is mesh-agnostic; the train-step builder
(launch/steps.build_program) installs this environment around tracing so
that models can pin key activations with logical constraints:

    x = axisenv.constrain(x, "batch", None, "model", None)

Logical names: "batch" -> the (pod, data) axes the batch is split over,
"model"/"kv" -> the tensor-parallel axis (dropped per-tensor when the
dimension is not divisible).  Without an installed environment every
constrain() is a no-op, so single-device smoke tests never see meshes.

Pinning these few points stops GSPMD from propagating bad shardings through
reshapes/gathers (observed: decode attention replicated over the model axis
and the KV cache all-gathered -- 16x flops + GBs of spurious traffic).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import PartitionSpec as P

_tls = threading.local()


def _env():
    return getattr(_tls, "env", None)


@contextmanager
def activation_axes(*, batch=(), batch_sizes=(), model=None, model_size=1,
                    mesh=None):
    """batch: tuple of mesh axis names; model: mesh axis name or None;
    mesh: the Mesh object (needed by shard_map-based layers)."""
    prev = _env()
    _tls.env = {
        "batch": tuple(batch), "batch_size": int(_prod(batch_sizes)),
        "model": model, "model_size": int(model_size), "mesh": mesh,
    }
    try:
        yield
    finally:
        _tls.env = prev


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def resolve(logical, dim: int):
    env = _env()
    if env is None or logical is None:
        return None
    if logical == "batch":
        if env["batch"] and dim % env["batch_size"] == 0:
            ax = env["batch"]
            return ax if len(ax) > 1 else ax[0]
        return None
    if logical in ("model", "kv"):
        if env["model"] and dim % env["model_size"] == 0:
            return env["model"]
        return None
    raise ValueError(logical)


def constrain(x, *logical):
    """Apply a with_sharding_constraint resolved from logical names.
    No-op when no environment is installed (plain CPU tests)."""
    env = _env()
    if env is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = P(*[resolve(l, d) for l, d in zip(logical, x.shape)])
    return jax.lax.with_sharding_constraint(x, spec)
