"""Mesh helpers shared by the launch code and the tests."""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.sharding.set_mesh(mesh)
