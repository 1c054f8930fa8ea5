"""Production mesh construction (TPU v5e pods).

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") —
the "pod" axis carries only batch (data) parallelism so no tensor
collective ever crosses the inter-pod DCN boundary.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "launch via launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by the
    CPU integration tests (subprocesses set
    --xla_force_host_platform_device_count)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def batch_axis_size(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
