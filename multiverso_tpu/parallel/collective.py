"""XLA collectives over the device mesh.

This is the TPU-native replacement for the reference's entire network layer
(ref: include/multiverso/net/, SURVEY.md §2.2): where Multiverso hand-rolls
Bruck allgather and recursive-halving reduce-scatter over MPI/ZMQ
point-to-point sends, the TPU data plane declares a ``lax.psum`` inside a
``shard_map`` over the mesh and lets XLA pick ICI-optimal collective
algorithms. ``net::Allreduce`` (ref: include/multiverso/net.h:51-57) maps
to ``allreduce_mesh``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..sharding import mesh as meshlib


@functools.lru_cache(maxsize=None)
def _allreduce_fn(mesh, ndim: int):
    """Sum-allreduce over every mesh axis; input arrives replicated
    per-device (each device holds a full copy = one 'rank contribution')."""
    axes = tuple(mesh.axis_names)
    spec = P(axes, *([None] * (ndim - 1))) if ndim else P()

    def body(x):
        return jax.lax.psum(x, axes)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=spec))


def allreduce_mesh(x, mesh=None):
    """Sum contributions laid shard-wise along the leading dim: the array's
    leading dim is split over the mesh, every shard is summed, and each
    shard of the result holds the total. For the common 'every chip has a
    full gradient' case, stack the per-chip arrays on axis 0."""
    mesh = mesh if mesh is not None else meshlib.local_mesh()
    x = jnp.asarray(x)
    return _allreduce_fn(mesh, x.ndim)(x)


@functools.lru_cache(maxsize=None)
def _psum_scalar_fn(mesh):
    axes = tuple(mesh.axis_names)
    return jax.jit(shard_map(lambda x: jax.lax.psum(x, axes),
                             mesh=mesh, in_specs=P(axes), out_specs=P(axes)))


def psum_scalar(value: float, mesh=None) -> float:
    """Each device contributes ``value``; returns value * n_devices. The
    tiniest ICI collective — used as a device-level barrier probe."""
    mesh = mesh if mesh is not None else meshlib.local_mesh()
    n = meshlib.device_count(mesh)
    contrib = jnp.full((n,), value, dtype=jnp.float32)
    return float(np.asarray(_psum_scalar_fn(mesh)(contrib))[0])


def pmean_mesh(x, mesh=None):
    """Mean-allreduce (model averaging over the mesh)."""
    mesh = mesh if mesh is not None else meshlib.local_mesh()
    n = meshlib.device_count(mesh)
    return allreduce_mesh(x, mesh) / n
