"""Device-mesh construction for multi-chip scaling.

The reference has no distributed layer of its own (SURVEY.md §2.3) — Spark
partitions data and UCX moves shuffle blocks in the host plugin.  The TPU-native
equivalent is a `jax.sharding.Mesh` with named axes:

- ``data``: partition parallelism — each device owns a slice of the rows of a
  columnar batch (the analog of Spark partitions mapped onto executors).
- ``model``: sharded auxiliary structures — e.g. a bloom filter's bit array or a
  broadcast-side hash table sharded across chips (tensor-parallel analog).

Collectives ride ICI within a pod slice and DCN across slices; XLA inserts them
from sharding annotations (`pjit`) or explicit `shard_map` collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    shape: Optional[Tuple[int, int]] = None,
    *,
    devices: Optional[Sequence] = None,
    axis_names: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS),
) -> Mesh:
    """Build a 2D (data, model) mesh.

    With no ``shape``, uses all devices as (n, 1) — pure partition parallelism.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices), 1)
    dp, mp = shape
    if dp * mp != len(devices):
        raise ValueError(f"mesh shape {shape} != device count {len(devices)}")
    arr = np.asarray(devices).reshape(dp, mp)
    return Mesh(arr, axis_names)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded over the data axis (leading dim), replicated over model."""
    return NamedSharding(mesh, P(DATA_AXIS))


def model_sharding(mesh: Mesh) -> NamedSharding:
    """A 1D structure (e.g. bloom bits) sharded over the model axis."""
    return NamedSharding(mesh, P(MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
