"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run overrides the
host platform device count before first jax init.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh stacks 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for multi-device CPU tests (8 fake devices)."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))


def _auto(n: int):
    """Auto axis types: the model code places arrays with
    ``with_sharding_constraint``, which Explicit axes reject."""
    return (jax.sharding.AxisType.Auto,) * n
