"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading pod=2 axis
    (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many devices this host actually has (tests,
    examples)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def make_xy_mesh():
    """(data, model) mesh over all local devices for the x/y grid
    decomposition — the one topology heuristic shared by the distributed
    stencil launcher and benchmarks (4 devices -> 2x2, 8 -> 4x2, ...)."""
    n = len(jax.devices())
    px = n // 2 if n >= 4 else n
    py = n // px
    return make_mesh((px, py), ("data", "model"))
