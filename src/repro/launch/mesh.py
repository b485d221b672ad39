"""Production mesh factory. A FUNCTION (not a module-level constant) so that
importing this module never touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types: the mesh entry point for
    launch scripts, tests and benches."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: 16x16 = 256 chips ("data","model"); multi-pod prepends a
    2-pod DCN axis (2,16,16) = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over host (CPU) devices for tests/benches."""
    return make_auto_mesh(shape, axes)
