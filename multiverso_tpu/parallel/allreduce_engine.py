"""Explicit collective algorithms over the ICI ring (AllreduceEngine parity).

The reference ships a from-scratch collective engine over point-to-point
sends (``src/net/allreduce_engine.cpp`` in the Multiverso reference):
payloads under 4KB (or with fewer elements than nodes) are allreduced by
allgather-then-local-reduce (``:31-44,57-77``); large payloads use
recursive-halving **reduce-scatter** (``:120-172``) followed by **Bruck
allgather** (``:90-117``); non-power-of-two node counts are handled by
pairing extras with group leaders (``allreduce_topo.cpp:58-150``).

This module re-expresses those algorithms TPU-natively: the point-to-point
primitive is ``jax.lax.ppermute`` over a mesh axis (each step compiles to one
ICI neighbour exchange), the per-rank topology maps the reference precomputes
(``BruckMap``/``RecursiveHalvingMap``) become step schedules unrolled at trace
time, and instead of the reference's divergent GroupLeader control flow,
non-power-of-two rings use a ring reduce-scatter — uniform SPMD control flow
is what the compiler wants. ``jax.lax.psum`` remains the production path
(``parallel.collectives``); this engine is the framework's drop-in alternative
for custom-topology experiments, exactly the role it plays in the reference.
"""

from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..topology import WORKER_AXIS
from .collectives import _mesh

from jax.sharding import PartitionSpec as P


# -- step schedules (the reference's BruckMap / RecursiveHalvingMap) ---------

def bruck_schedule(n: int) -> List[Tuple[int, int]]:
    """Bruck allgather steps for an ``n`` ring: list of (distance,
    blocks_to_send). ``ceil(log2 n)`` steps, doubling block counts, with a
    truncated final step when ``n`` is not a power of two
    (``allreduce_topo.cpp:20`` BruckMap::Construct)."""
    steps = []
    m = 1
    while m < n:
        steps.append((m, min(m, n - m)))
        m *= 2
    return steps


def recursive_halving_schedule(n: int) -> List[int]:
    """Pair distances for recursive-halving reduce-scatter; empty when ``n``
    is not a power of two (those sizes take the ring path instead of the
    reference's GroupLeader pairing, ``allreduce_topo.cpp:58-150``)."""
    if n & (n - 1):
        return []
    steps = []
    d = n // 2
    while d >= 1:
        steps.append(d)
        d //= 2
    return steps


class AllreduceEngine:
    """Allgather / ReduceScatter / Allreduce built from ppermute steps
    (``include/multiverso/net/allreduce_engine.h:80-147``).

    Array conventions match ``parallel.collectives``: inputs carry one row
    per ring participant along axis 0, sharded over ``axis``.
    """

    SMALL_PAYLOAD_BYTES = 4096  # reference's allgather-allreduce cutoff

    def __init__(self, axis: str = WORKER_AXIS, mesh=None) -> None:
        self.axis = axis
        self.mesh = _mesh(mesh)
        self.n = int(self.mesh.shape[axis])

    # -- in-SPMD building blocks ------------------------------------------
    def _bruck_gather(self, block):
        """Inside shard_map: gather every participant's ``block`` (leading
        dim ``c``) into ``[n*c, ...]`` ordered by rank."""
        axis, n = self.axis, self.n
        c = block.shape[0]
        idx = jax.lax.axis_index(axis)
        buf = block
        for dist, send_blocks in bruck_schedule(n):
            send = buf[: send_blocks * c]
            perm = [(i, (i - dist) % n) for i in range(n)]
            recv = jax.lax.ppermute(send, axis, perm)
            buf = jnp.concatenate([buf, recv], axis=0)
        # buf rows are blocks [i, i+1, ..., i+n-1]; rotate block b to row b.
        return jnp.roll(buf, idx * c, axis=0)

    def _halving_reduce_scatter(self, vec):
        """Inside shard_map: recursive-halving RS of the full-size ``vec``
        (leading dim divisible by n); returns this rank's reduced chunk."""
        axis, n = self.axis, self.n
        idx = jax.lax.axis_index(axis)
        buf = vec
        for d in recursive_halving_schedule(n):
            half = buf.shape[0] // 2
            pair = buf.reshape((2, half) + buf.shape[1:])
            side = (idx // d) % 2  # my address bit at this distance
            keep = pair[side]
            send = pair[1 - side]
            perm = [(i, i ^ d) for i in range(n)]
            buf = keep + jax.lax.ppermute(send, axis, perm)
        return buf

    def _ring_reduce_scatter(self, vec):
        """Inside shard_map: ring RS for any ring size (n-1 neighbour steps);
        returns this rank's reduced chunk."""
        axis, n = self.axis, self.n
        idx = jax.lax.axis_index(axis)
        c = vec.shape[0] // n
        buf = vec.reshape((n, c) + vec.shape[1:])
        fwd = [(i, (i + 1) % n) for i in range(n)]
        # schedule starts one chunk behind the owner so that after the n-1
        # neighbour steps rank i holds fully-reduced chunk i directly (no
        # extra handoff ppermute)
        for s in range(n - 1):
            outgoing = buf[(idx - s - 1) % n]
            recv = jax.lax.ppermute(outgoing, axis, fwd)
            buf = buf.at[(idx - s - 2) % n].add(recv)
        return buf[idx]

    def _reduce_scatter_shard(self, vec):
        if recursive_halving_schedule(self.n):
            return self._halving_reduce_scatter(vec)
        return self._ring_reduce_scatter(vec)

    # -- public ops --------------------------------------------------------
    def allgather(self, x):
        """[n*c, ...] sharded over axis → same value replicated everywhere
        (``AllreduceEngine::Allgather``, Bruck)."""
        spec = P(self.axis, *(None,) * (np.ndim(x) - 1))

        @partial(jax.shard_map, mesh=self.mesh, in_specs=(spec,),
                 out_specs=P(*(None,) * np.ndim(x)), check_vma=False)
        def _ag(shard):
            return self._bruck_gather(shard)

        return _ag(x)

    def reduce_scatter(self, x):
        """[n, k, ...] (row i = participant i's contribution, k divisible by
        n) → [k, ...] summed, sharded over axis
        (``AllreduceEngine::ReduceScatter``)."""
        n = self.n
        if x.shape[0] != n or x.shape[1] % n != 0:
            raise ValueError(
                f"reduce_scatter expects [n={n}, k*n, ...], got {tuple(x.shape)}")
        in_spec = P(self.axis, *(None,) * (np.ndim(x) - 1))
        out_spec = P(self.axis, *(None,) * (np.ndim(x) - 2))

        @partial(jax.shard_map, mesh=self.mesh, in_specs=(in_spec,),
                 out_specs=out_spec, check_vma=False)
        def _rs(shard):
            return self._reduce_scatter_shard(shard[0])

        return _rs(x)

    def allreduce(self, x):
        """[n, k, ...] (row i = participant i's full-size buffer) → [n, k, ...]
        where every row is the elementwise sum (``AllreduceEngine::Allreduce``).

        Payloads under ``SMALL_PAYLOAD_BYTES`` (or with fewer elements than
        ring participants) take the allgather-allreduce path; larger ones
        reduce-scatter + allgather, both cutoffs as in the reference
        (``allreduce_engine.cpp:31-44``). Element counts that don't divide
        the ring size are zero-padded for the scatter and sliced after.
        """
        n = self.n
        if x.shape[0] != n:
            raise ValueError(f"allreduce expects [n={n}, ...], got {tuple(x.shape)}")
        k = int(np.prod(x.shape[1:]))
        payload = k * x.dtype.itemsize
        spec = P(self.axis, *(None,) * (np.ndim(x) - 1))

        if payload < self.SMALL_PAYLOAD_BYTES or k < n:
            @partial(jax.shard_map, mesh=self.mesh, in_specs=(spec,),
                     out_specs=spec, check_vma=False)
            def _ar_small(shard):
                gathered = self._bruck_gather(shard)  # [n, k...]
                return jnp.sum(gathered, axis=0, keepdims=True)

            return _ar_small(x)

        @partial(jax.shard_map, mesh=self.mesh, in_specs=(spec,),
                 out_specs=spec, check_vma=False)
        def _ar(shard):
            # Ravel so the scatter dimension is the full element count (the
            # trailing dims of a multi-dim payload need not divide n), and
            # zero-pad to a multiple of the ring size.
            flat = shard[0].reshape(-1)
            pad = -flat.shape[0] % n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            chunk = self._reduce_scatter_shard(flat)
            full = self._bruck_gather(chunk)
            if pad:
                full = full[:-pad]
            return full.reshape(shard.shape)

        return _ar(x)
