"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state -- the dry-run process
must set XLA_FLAGS before the first jax call, and tests must keep seeing a
single CPU device.
"""

from __future__ import annotations

import numpy as np
import jax

__all__ = ["make_production_mesh", "make_mesh", "batch_axes", "AXES"]

AXES = {"single": ("data", "model"), "multi": ("pod", "data", "model")}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} "
            f"(dry-runs must set XLA_FLAGS=--xla_force_host_platform_device_count=...)"
        )
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch shards over (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
