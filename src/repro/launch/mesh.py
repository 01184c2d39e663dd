"""Production meshes. A FUNCTION (not a module constant) so importing this
module never touches jax device state (required by the dry-run contract).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _auto(shape):
    # the sharding rules place activations with with_sharding_constraint,
    # which needs Auto (compiler-propagated) axes
    return (AxisType.Auto,) * len(shape)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(shape))
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            "sets this automatically)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=_auto(shape))


def make_mesh(shape, axes):
    """Elastic helper: arbitrary mesh over a prefix of available devices."""
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=_auto(shape))
