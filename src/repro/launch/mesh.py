"""Production mesh construction.

Called as a FUNCTION so importing this module never touches jax device
state.  Single-pod: 16 x 16 = 256 chips ("data", "model"); multi-pod:
2 x 16 x 16 = 512 chips ("pod", "data", "model") — the pod axis is the
slow (DCN) dimension, so sharding rules only ever place the batch on
it.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale SPMD tests (host platform devices)."""
    return _mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
