"""Production mesh construction. A FUNCTION (not a module constant) so that
importing this module never touches jax device state."""
from __future__ import annotations

import jax


def _make(shape, axes):
    """jax.make_mesh with auto axis types on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e pod slice); multi-pod adds a
    leading pod axis (2×16×16 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper for tests/examples (auto axis types)."""
    return _make(shape, axes)


__all__ = ["make_production_mesh", "make_mesh"]
