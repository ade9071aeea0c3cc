"""Production mesh construction.

NOTE: this module never touches jax device state at import time —
``make_production_mesh`` is a function (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before importing jax,
and smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU-sharded integration tests (8 host devices)."""
    return _mesh((n_data, n_model), ("data", "model"))
