"""Production meshes for the multi-pod dry-run.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run entry point must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the
first jax call, and smoke tests must keep seeing 1 device.

Both builders make ``Auto`` axes: arrays are placed through ``jit``
shardings and the partitioner propagates layouts (GSPMD), the model the
sharding rules in ``repro/launch/sharding.py`` are written for.  Install
a mesh for the in-graph constraints with ``jax.set_mesh(mesh)``.

Target hardware (roofline constants in launch/roofline.py): TPU v5e pods,
256 chips/pod, 16x16 single-pod mesh (data, model) and a 2-pod 512-chip
mesh (pod, data, model).  FL clients map onto the data axis — 16 clients
single-pod, 32 (pod x data collapsed) multi-pod.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def client_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that enumerate FL clients."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_clients(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n


def client_axis_spec(mesh):
    """PartitionSpec entry for a client-indexed dim: the client axes as a
    tuple when several enumerate clients (multi-pod), else the single axis
    name — the spelling every client-axis sharding rule shares."""
    ca = client_axes(mesh)
    return ca if len(ca) > 1 else ca[0]


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small host mesh over the first ``data * model`` devices: virtual
    CPU devices in tests, or the chips of one host (``(4, 1)`` on a 2x2
    TPU v5e host)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
