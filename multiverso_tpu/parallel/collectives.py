"""Device-level collectives over the mesh (ICI data plane).

TPU-native replacement for the reference communication backend
(``src/net/mpi_net``/``zmq_net`` point-to-point transports and the hand-rolled
``AllreduceEngine`` — Bruck allgather + recursive-halving reduce-scatter,
``src/net/allreduce_engine.cpp:31-172`` in the Multiverso reference). Every
algorithm there exists to move bytes between processes; here the same
operations are XLA collectives compiled onto ICI links: ``psum`` (allreduce),
``all_gather``, ``psum_scatter`` (reduce-scatter), ``all_to_all`` and
``ppermute`` (the ring primitive). The topology mapping the reference
precomputes per rank (``allreduce_topo.cpp``) is XLA's job.

Functions here wrap ``shard_map`` so callers can allreduce host-shaped arrays
without writing SPMD code; jitted training steps should instead rely on
sharding propagation (see ``parallel.sync_step``) or use ``jax.lax``
collectives directly inside their own ``shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import Session
from ..topology import WORKER_AXIS

from jax.sharding import PartitionSpec as P


def _mesh(mesh=None):
    return mesh if mesh is not None else Session.get().mesh


def allreduce(x, axis: str = WORKER_AXIS, mesh=None, mean: bool = False):
    """Sum (or mean) ``x`` across ``axis``; ``x`` is sharded along axis 0.

    The TPU form of ``MV_Aggregate``/``net::Allreduce``
    (``src/multiverso.cpp:47-50``): one ``psum`` riding ICI.
    """
    mesh = _mesh(mesh)
    spec = P(axis, *(None,) * (np.ndim(x) - 1))

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec,
             check_vma=False)
    def _reduce(shard):
        total = jax.lax.psum(shard, axis)
        if mean:
            total = total / mesh.shape[axis]
        return total

    return _reduce(x)


def allreduce_replicated(x, axis: str = WORKER_AXIS, mesh=None, mean: bool = False):
    """Allreduce of a per-device value that is already replicated layout-wise:
    each worker contributes its shard along a new leading axis."""
    mesh = _mesh(mesh)
    all_axes = tuple(mesh.axis_names)
    other = tuple(a for a in all_axes if a != axis)
    spec = P()

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, *(None,) * np.ndim(x)),),
             out_specs=spec, check_vma=False)
    def _reduce(shard):
        total = jax.lax.psum(shard[0], axis)
        if mean:
            total = total / mesh.shape[axis]
        return total

    stacked = jnp.broadcast_to(x, (mesh.shape[axis],) + tuple(np.shape(x)))
    return _reduce(stacked)


def all_gather(x, axis: str = WORKER_AXIS, mesh=None):
    """Gather shards along ``axis`` onto every participant (Bruck allgather
    equivalent, ``allreduce_engine.cpp:90-117``)."""
    mesh = _mesh(mesh)
    spec = P(axis, *(None,) * (np.ndim(x) - 1))

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
             out_specs=P(*(None,) * np.ndim(x)), check_vma=False)
    def _gather(shard):
        return jax.lax.all_gather(shard, axis, axis=0, tiled=True)

    return _gather(x)


def reduce_scatter(x, axis: str = WORKER_AXIS, mesh=None):
    """Reduce-scatter (recursive-halving equivalent,
    ``allreduce_engine.cpp:120-172``): ``x`` is ``[n, k, ...]`` where row i is
    participant i's full-size contribution (``k`` divisible by ``n``); returns
    ``[k, ...]`` — the elementwise sum, laid out sharded over ``axis`` so each
    participant holds its ``k/n`` slice.
    """
    mesh = _mesh(mesh)
    n = mesh.shape[axis]
    if x.shape[0] != n or x.shape[1] % n != 0:
        raise ValueError(
            f"reduce_scatter expects [n={n}, k*n, ...], got {tuple(x.shape)}")
    in_spec = P(axis, *(None,) * (np.ndim(x) - 1))
    out_spec = P(axis, *(None,) * (np.ndim(x) - 2))

    @partial(jax.shard_map, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
             check_vma=False)
    def _rs(shard):
        return jax.lax.psum_scatter(shard[0], axis, scatter_dimension=0,
                                    tiled=True)

    return _rs(x)


def ring_shift(x, axis: str, mesh=None, shift: int = 1):
    """Rotate shards around the ``axis`` ring by ``shift`` (ppermute) — the
    building block ring attention and pipelined collectives share."""
    mesh = _mesh(mesh)
    n = mesh.shape[axis]
    spec = P(axis, *(None,) * (np.ndim(x) - 1))
    perm = [(i, (i + shift) % n) for i in range(n)]

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec,
             check_vma=False)
    def _shift(shard):
        return jax.lax.ppermute(shard, axis, perm)

    return _shift(x)
