"""Mesh construction + sharding helpers.

One logical axis ``part`` shards the key space (storage partitions); an
optional second axis ``rep`` replicates for read scaling / shards the watcher
table — mirroring the reference's reader-replica parallelism (SURVEY P6).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(
    n_devices: int | None = None, axes: tuple[str, ...] = ("part",), shape: tuple[int, ...] | None = None
) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axes) - 1)
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axes)


def partition_spec(mesh: Mesh, *axis_names: str | None) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*axis_names))
