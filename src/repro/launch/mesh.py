"""Mesh construction for single-pod and multi-pod deployments.

All builders are FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh spans 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh for smoke tests / examples on however many devices exist."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
