"""Production meshes: 16x16 single pod (256 chips) and 2x16x16 (512 chips).

Defined as FUNCTIONS so importing this module never touches jax device
state. Axes: "pod" x "data" compose into the batch dimension (the gradient
all-reduce crosses pods exactly once per step); "model" carries TP/EP.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (the compiler propagates
    shardings from the constraints the model code places)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Whatever this host has, as (data, model) — for tests/examples."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
