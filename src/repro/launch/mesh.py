"""Mesh builders (functions — importing never touches jax devices)."""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A ``shape`` mesh over the first ``prod(shape)`` devices, every axis
    ``Auto`` (the partitioner places what the programs do not annotate).
    Fewer devices than the process holds is the in-process elastic
    scale-down."""
    n = math.prod(int(s) for s in shape)
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices but "
                         f"only {len(devs)} are available")
    return jax.make_mesh(tuple(int(s) for s in shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def mesh_world(mesh) -> int:
    return int(mesh.devices.size)
