"""Sharding hints for activations inside model code.

Model code is mesh-agnostic; the launcher registers the active mesh axis
sizes before tracing (`set_mesh_axes`), and `constrain` applies
`with_sharding_constraint` only when (a) axes are registered and (b) every
named axis divides the corresponding dim. Otherwise it is the identity, so
tests and single-device runs are untouched.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def set_mesh_axes(axes: Optional[Dict[str, int]]) -> None:
    _state.axes = dict(axes) if axes else None


def set_mesh(mesh) -> None:
    """Register `mesh`'s axis sizes."""
    set_mesh_axes(dict(mesh.shape))


def get_mesh_axes() -> Optional[Dict[str, int]]:
    return getattr(_state, "axes", None)


def axis_size(name: Union[str, Sequence[str]]) -> int:
    axes = get_mesh_axes() or {}
    if isinstance(name, str):
        return axes.get(name, 1)
    n = 1
    for a in name:
        n *= axes.get(a, 1)
    return n


def batch_spec_axes():
    axes = get_mesh_axes() or {}
    return ("pod", "data") if "pod" in axes else ("data",)


def constrain(x, *spec):
    """with_sharding_constraint(x, P(*spec)) when legal, else identity.
    Each spec entry: None | axis name | tuple of axis names."""
    axes = get_mesh_axes()
    if axes is None:
        return x
    clean = []
    for dim, entry in zip(x.shape, spec):
        if entry is None:
            clean.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        if not all(n in axes for n in names):
            clean.append(None)
            continue
        size = 1
        for n in names:
            size *= axes[n]
        clean.append(entry if dim % size == 0 else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*clean))
    except Exception:  # no mesh context at trace time
        return x
