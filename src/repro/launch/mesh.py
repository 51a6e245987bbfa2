"""Production mesh factories.

A FUNCTION, not a module constant, so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    place arrays with ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes ``jax.make_mesh`` creates by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh (CPU tests / examples): axes exist, size 1."""
    return make_mesh((1, 1), ("data", "model"))
