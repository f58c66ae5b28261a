"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init and only then calls make_production_mesh().
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_auto_mesh", "make_device_mesh",
           "dp_axes", "MP_AXIS"]

MP_AXIS = "model"


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` over all devices with Auto axis types."""
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_device_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    """A mesh over an explicit device subset — the elastic-restart path:
    after a (simulated) device loss the supervisor rebuilds its dist engine
    over the survivors, which ``jax.make_mesh`` (always all devices) can't
    express."""
    need = int(np.prod(shape))
    if len(devices) < need:
        raise ValueError(f"mesh shape {shape} needs {need} devices, "
                         f"got {len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices[:need]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def dp_axes(mesh: jax.sharding.Mesh):
    """The data-parallel axes of a mesh: ("pod","data") or ("data",)."""
    return tuple(a for a in mesh.axis_names if a != MP_AXIS)
