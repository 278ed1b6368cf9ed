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


@functools.lru_cache(maxsize=None)
def _uniform_fn(shape: Tuple[int, int], dtype, sharding: NamedSharding,
                rows: int, cols: int):
    def init(seed_words, stream, lo, hi):
        with jax.named_scope("mv.table.init"):
            key = jax.random.fold_in(
                jax.random.wrap_key_data(seed_words, impl="threefry2x32"),
                stream)
            drawn = jax.random.uniform(key, shape, jax.numpy.float32, lo, hi)
            # uniform() rounds lo + u * (hi - lo) to nearest, which can
            # reach hi itself; the interval is half-open.
            drawn = jax.numpy.minimum(drawn, jax.numpy.nextafter(hi, lo))
            inside = ((jax.lax.broadcasted_iota(np.int32, shape, 0) < rows)
                      & (jax.lax.broadcasted_iota(np.int32, shape, 1) < cols))
            return jax.numpy.where(inside, drawn, 0).astype(dtype)

    return jax.jit(init, out_shardings=sharding)


def uniform_sharded(shape: Tuple[int, int], dtype, sharding: NamedSharding,
                    rows: int, cols: int, lo: float, hi: float,
                    seed: int, stream: int):
    """A ``shape`` array laid out shard-wise whose ``[:rows, :cols]`` is
    uniform in ``[lo, hi)`` and whose padding is zero, drawn on the
    devices by one program: every shard writes its own rows, nothing is
    drawn or staged on the host and nothing crosses devices.

    The values are float32 draws cast to ``dtype``, a function of
    ``(seed, stream)``, the element's position and the stored width
    ``shape[1]`` only: the same on one device, on several, and on any
    backend, whatever the row padding (tests/test_table_init.py holds
    that). They are JAX's threefry stream, not numpy's. The jitted
    program is cached per layout; seed, stream and bounds are its
    arguments, so a new seed compiles nothing."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    draw = _uniform_fn(tuple(shape), np.dtype(dtype).name, sharding,
                       int(rows), int(cols))
    # The partitionable threefry derives an element's bits from its flat
    # index alone, so each shard computes its own rows with no
    # collective. JAX's default; held on here (tracing and lowering both
    # read it) whatever the process has set.
    with jax.threefry_partitionable(True):
        return draw(words, np.uint32(stream), np.float32(lo), np.float32(hi))
