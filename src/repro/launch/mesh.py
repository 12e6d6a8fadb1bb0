"""Production mesh construction (assignment §MULTI-POD DRY-RUN).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. Axes are ``Auto``: the sharding specs in
``launch/sharding.py`` and the ``with_sharding_constraint`` hints in the
models leave propagation to the partitioner, which ``Explicit`` axes (the
``jax.make_mesh`` default) would refuse. Callers enter a mesh with
``with jax.set_mesh(mesh):``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

AXES_SINGLE = ("data", "model")
AXES_MULTI = ("pod", "data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = AXES_MULTI if multi_pod else AXES_SINGLE
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=AXES_SINGLE):
    """Small host-device mesh for tests (XLA_FLAGS device_count >= prod(shape))."""
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

