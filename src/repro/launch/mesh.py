"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def production_mesh_spec(*, multi_pod: bool = False):
    """(shape, axes) of the production mesh — pure, testable without devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shape, axes


def make_production_mesh(*, multi_pod: bool = False):
    shape, axes = production_mesh_spec(multi_pod=multi_pod)
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests of the sharded code paths."""
    return make_mesh((1, 1), ("data", "model"))
