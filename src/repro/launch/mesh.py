"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module-level constant: importing this module never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.  Since jax 0.7 the
    default axis type is ``Explicit``, under which the sharding rules'
    ``with_sharding_constraint`` calls (``dist/sharding.py``) are refused;
    the rules are written for GSPMD propagation over Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    set before jax init in the test process)."""
    return make_mesh(shape, axes)
