"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. Shapes target TPU v5e: a 16x16 pod (256 chips,
axes data x model) and a 2-pod system (512 chips, pod x data x model)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: shardings are propagated by the compiler and constrained
    # with with_sharding_constraint (repro.dist.sharding); jax.make_mesh
    # would otherwise build Explicit axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host actually has (tests/examples on CPU)."""
    n = len(jax.devices())
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))
