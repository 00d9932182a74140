"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count locks on first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) data x model = 256 chips (TPU v5e pod slice).
    Multi-pod: (2, 16, 16) pod x data x model = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(model_size: int = 1):
    """1-device mesh for CPU tests of the sharded code paths."""
    return jax.make_mesh((1, model_size), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
