"""Mesh context + sharding-constraint helpers.

Model code never imports jax.sharding directly: it calls `constrain(x, *spec)`
with logical axis names ("model", the DP tuple from `ctx_dp_axes()`) and this
module translates against whatever mesh is ambient — a no-op when none is.

The ambient mesh is the one bound by `jax.set_mesh` (re-exported here as
`set_mesh`); `ctx_mesh` reads it back through `jax.sharding.get_abstract_mesh`.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["DP_AXES", "ctx_mesh", "ctx_dp_axes", "constrain", "set_mesh"]

# Axes that compose into the batch (data-parallel) dimension, in mesh order.
DP_AXES = ("pod", "data")


def ctx_mesh():
    """The ambient (abstract) mesh, or None outside any `set_mesh` block."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def ctx_dp_axes() -> Tuple[str, ...]:
    """Data-parallel axes of the ambient mesh ( () without a mesh )."""
    m = ctx_mesh()
    if m is None:
        return ()
    return tuple(a for a in m.axis_names if a in DP_AXES)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; identity without one.

    Spec entries are axis names, tuples of axis names, or None; entries naming
    axes the ambient mesh lacks are dropped (so "model" hints are safe on a
    data-only mesh).
    """
    m = ctx_mesh()
    if m is None:
        return x
    names = set(m.axis_names)

    def _keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    entries = tuple(_keep(e) for e in spec)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))


set_mesh = jax.set_mesh
