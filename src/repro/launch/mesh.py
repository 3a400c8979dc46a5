"""Mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing
jax; everything else sees the real device count.

Every mesh is built with ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes (sharding in types), but the train step is written for
GSPMD propagation: ``with_sharding_constraint`` pins, ``shard_map``
combines and vmaps over a server dim that is sharded while the per-client
batch is not.  Explicit axes reject all three.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU integration tests (8 forced host devices)."""
    return _mesh(shape, axes)


def make_device_mesh(devices=None):
    """One GFL server per device: ``data`` = device count, ``model`` = 1."""
    devices = jax.devices() if devices is None else list(devices)
    return _mesh((len(devices), 1), ("data", "model"), devices=devices)


def server_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that carry the GFL server dimension."""
    return tuple(a for a in mesh.axis_names if a != "model")


def num_servers(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in server_axes(mesh)]))
