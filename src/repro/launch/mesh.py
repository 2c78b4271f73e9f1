"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``. The train and serve steps
    place activations with ``with_sharding_constraint``, which only accepts
    Auto axes (``jax.make_mesh`` defaults to Explicit ones). Enter the mesh
    with ``jax.set_mesh(mesh)``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2x16x16 = 512
    chips (pod, data, model); 'pod' is the outer DP axis crossing the
    inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))
