"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: JAX 0.9 defaults to Explicit axes,
    under which the COW publish gather and the DHT's device_put of stacked
    shard state need explicit shardings. ``devices`` defaults to all of
    ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
    outer data-parallel axis crossing DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small host-device mesh for subprocess tests (8 fake devices)."""
    return auto_mesh((data, model), ("data", "model"))
