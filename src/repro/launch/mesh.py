"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Production: single pod 16x16 = 256 chips
("data","model"); multi-pod 2 pods x 256 = 512 chips ("pod","data","model").
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: model code places
    activations through ``with_sharding_constraint``
    (``parallel.sharding.constrain``), which may name only Auto axes —
    ``jax.make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for tests (requires enough local/fake devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
