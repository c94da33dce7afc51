"""Production mesh definitions.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and then calls these.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: GSPMD propagates shardings and
    ``with_sharding_constraint`` may name any axis (``jax.make_mesh``
    defaults to Explicit axes, which refuse both)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests and examples."""
    return _auto_mesh((1, 1), ("data", "model"))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= int(v)
    return n
