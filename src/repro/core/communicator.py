"""Per-task communicator construction.

In Deep RC (paper), the RemoteAgent builds an MPI/GLOO/NCCL communicator
with N ranks for each task *at runtime, in constant time* — the measured
3–8 s overhead of Table 2.  The TPU-native analogue: carve a
``jax.sharding.Mesh`` over a slice of the pilot's devices.  Mesh
construction is pure host-side metadata (O(1) in chips), which is how the
design *preserves* the constant-overhead property; ``benchmarks/
overheads.py`` measures it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass
class Communicator:
    """What a task receives: its mesh plus metadata (cf. an MPI comm)."""

    mesh: Mesh
    backend: str  # "ici" on TPU; "host" on CPU placeholders
    build_time_s: float
    devices: Tuple
    # which pilot's pool this mesh was carved from (None for meshes built
    # outside the pilot runtime).  Task fns and the migration tests use it
    # to observe *where* an attempt actually ran.
    pilot_uid: Optional[str] = None

    @property
    def size(self) -> int:
        return len(self.devices)

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    def describe(self) -> dict:
        return {"pilot": self.pilot_uid, "backend": self.backend,
                "size": self.size, "device_ids": [d.id for d in self.devices]}


def build_communicator(
    devices: Sequence,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    mesh_axes: Tuple[str, ...] = ("data",),
    pilot_uid: Optional[str] = None,
) -> Communicator:
    t0 = time.time()
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = (n,)
    want = 1
    for s in mesh_shape:
        want *= s
    if want != n:
        raise ValueError(f"mesh shape {mesh_shape} needs {want} devices, got {n}")
    arr = np.asarray(devices).reshape(mesh_shape)
    mesh = Mesh(arr, mesh_axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_axes))
    backend = "ici" if devices and devices[0].platform == "tpu" else "host"
    return Communicator(mesh, backend, time.time() - t0, tuple(devices),
                        pilot_uid=pilot_uid)
