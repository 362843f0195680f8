"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state. The dry-run forces 512 host devices via XLA_FLAGS *before*
first jax init; tests use small meshes in subprocesses.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: activations are placed by with_sharding_constraint and the
    # compiler, which jax.make_mesh's default Explicit axes reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic rescale)."""
    return _auto_mesh(tuple(shape), tuple(axes))
