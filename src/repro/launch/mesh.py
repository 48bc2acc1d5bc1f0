"""Mesh construction (FUNCTIONS — importing this module never touches jax
device state)."""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types: shardings propagate through
    the program as GSPMD infers them (``jax.make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU integration tests (requires host-device override)."""
    return make_mesh((data, model), ("data", "model"))
