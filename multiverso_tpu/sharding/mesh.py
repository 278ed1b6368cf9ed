"""Device-mesh helpers for table storage.

This is where the TPU-native build departs hardest from the reference: the
reference shards tables across *server processes* connected by MPI/ZMQ
(ref: src/table/array_table.cpp:98-108); here each server shard is
additionally a sharded ``jax.Array`` laid out over the local TPU mesh, so
updater arithmetic runs data-parallel over ICI with XLA-inserted
collectives. A 1-D mesh with axis ``"shard"`` covers HBM placement of table
state; model-parallel axes (dp/tp/pp/sp) are built on top by apps via
``make_mesh``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"


@functools.lru_cache(maxsize=None)
def local_mesh(num_devices: Optional[int] = None) -> Mesh:
    """A 1-D mesh over (a prefix of) THIS process's devices. Under
    jax.distributed ``jax.devices()`` also lists the other processes'
    devices; a table laid over those would turn every server-side jit
    into a multi-process program that all ranks must launch in
    lockstep, which independent server actors do not."""
    devices = jax.local_devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def describe_backend() -> str:
    """The default backend as JAX reports it — what every entry point
    logs at start, so a run on the CPU cannot pass for a run on the
    chip."""
    devices = jax.devices()
    return (f"platform={devices[0].platform} "
            f"device_kind={devices[0].device_kind} "
            f"devices={len(devices)}")


def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """Build an N-D mesh (dp/tp/pp/...) over the given devices."""
    devices = np.array(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(tuple(axis_sizes)), tuple(axis_names))


def sharded_1d(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(SHARD_AXIS))


def row_sharded(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(SHARD_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def padded_size(n: int, num_shards: int) -> int:
    """Smallest multiple of num_shards >= n (even HBM shards; the logical
    size is tracked separately, mirroring how the reference gives the last
    server the remainder, ref: src/table/array_table.cpp:98-108)."""
    if num_shards <= 0:
        return n
    return ((n + num_shards - 1) // num_shards) * num_shards


def device_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


@functools.lru_cache(maxsize=None)
def _zeros_fn(shape: Tuple[int, ...], dtype, sharding: NamedSharding):
    return jax.jit(lambda: jax.numpy.zeros(shape, dtype),
                   out_shardings=sharding)


def zeros_sharded(shape: Tuple[int, ...], dtype, sharding: NamedSharding):
    """Allocate a zero array already laid out shard-wise (no host roundtrip).

    The underlying jitted constructor is cached per (shape, dtype,
    sharding) so repeated table creation does not retrace."""
    return _zeros_fn(tuple(shape), np.dtype(dtype).name, sharding)()
