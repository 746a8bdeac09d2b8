"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def _mesh(dev_array, axes):
    return jax.sharding.Mesh(
        dev_array, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = (data, model) = 256 chips.
    Multi-pod: (2, 16, 16) = (pod, data, model) = 512 chips.

    The dry-run container exposes 512 host placeholder devices; the
    single-pod mesh uses the first 256 so both meshes build from one
    process.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)"
        )
    import numpy as np

    dev_array = np.asarray(devices).reshape(shape)
    return _mesh(dev_array, axes)


def make_mesh(shape, axes):
    """Small helper for tests / examples on few host devices."""
    import numpy as np

    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    dev_array = np.asarray(devices).reshape(shape)
    return _mesh(dev_array, axes)
