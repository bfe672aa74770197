"""Production meshes. Functions only — importing this module never touches
jax device state."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(devices, shape):
    """("data", "model") mesh of `shape` over the given devices."""
    return _make_mesh(shape, ("data", "model"), devices=list(devices))


def make_debug_mesh(devices: int = 8):
    """Small mesh for CPU tests: (devices//2, 2) ("data", "model")."""
    assert devices % 2 == 0
    return _make_mesh((devices // 2, 2), ("data", "model"))
