"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required: the dry-run pins the device count via
XLA_FLAGS before any jax initialization; tests and benches must keep
seeing 1 CPU device).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.common.config import MeshConfig, MULTI_POD_MESH, SINGLE_POD_MESH


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_from_config(mc: MeshConfig):
    return _auto_mesh(mc.shape, mc.axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for CPU tests (requires host-platform device override)."""
    return _auto_mesh(shape, axes)


def mesh_config(multi_pod: bool) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH
