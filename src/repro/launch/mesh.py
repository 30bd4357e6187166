"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything here just consumes whatever devices exist.

Mesh layout (TPU v5e pods of 16x16 = 256 chips):

* single-pod : (data=16, model=16)
* multi-pod  : (pod=P, data=16, model=16) -- "pod" composes with "data" for
  batch sharding (DCN-ish axis), "model" stays intra-pod (ICI).

``make_production_mesh`` takes arbitrary pod counts for elastic scale-out;
the dry-run exercises P=2 (512 chips).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2) -> Mesh:
    shape = (n_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(shape: Tuple[int, ...] = (1, 1), axes=("data", "model")) -> Mesh:
    """Tiny mesh for CPU smoke tests (1 device)."""
    return _make_mesh(shape, tuple(axes))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes over which the global batch shards."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def shard_devices(n_shards: int, devices: Optional[Sequence] = None) -> list:
    """Round-robin shard -> device placement for cluster serving.

    Shard broker ``i`` of a :class:`repro.serving.cluster.Cluster` pins
    its cache state to ``devices[i % len(devices)]`` so shard serves
    overlap on hardware when the backend has more than one device.  With
    fewer devices than shards, shards wrap (several brokers share a
    device); with one device this degenerates to today's single-device
    placement.  ``devices`` defaults to ``jax.devices()``.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if not devs:
        raise ValueError("no devices available for shard placement")
    n = int(n_shards)
    if n < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return [devs[i % len(devs)] for i in range(n)]
