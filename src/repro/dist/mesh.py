"""Mesh construction — production pod shapes and the host test mesh.

FUNCTIONS, not module constants — importing this module never touches jax
device state (device count is locked at first backend init, and the dry-run
needs to set XLA_FLAGS before that happens).

Import from ``repro.dist`` (the ``repro.launch.mesh`` re-export shim is
gone).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules in
    :mod:`repro.dist.sharding` place values with ``with_sharding_constraint``
    and let GSPMD propagate, which Explicit axes (the jax default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch-sharding axes for a mesh: ('data',) or ('pod','data')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU multi-device tests (8 forced host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
