"""Ulysses-style all-to-all sequence parallelism (head-resharded attention).

The second canonical long-context scheme next to ring attention
(``ops/ring_attention.py``): instead of rotating K/V blocks around the ICI
ring, two ``all_to_all`` collectives reshard the activations from
sequence-sharded to head-sharded and back (DeepSpeed-Ulysses, Jacobs et al.,
2023):

  1. q/k/v arrive ``[seq/S, H, d]`` per device (sequence sharded over the
     ``seq`` mesh axis);
  2. ``all_to_all`` (split heads, concat sequence) gives each device the
     FULL sequence for ``H/S`` of the heads;
  3. exact attention runs locally per head — one big MXU matmul chain, no
     per-step collectives;
  4. the reverse ``all_to_all`` restores sequence sharding over all heads.

Compared to ring attention: 2 collectives total instead of S ``ppermute``
steps (better when heads >= devices and the sequence fits in HBM per
device), but requires ``H % S == 0`` where the ring has no head constraint.
Differentiable end-to-end (AD transposes the all_to_alls).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from ..topology import SEQ_AXIS
from .ring_attention import _prefix_chunk_attn, reference_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "xla",
    **attn_kwargs,
) -> jax.Array:
    """Exact attention with sequence sharded over ``axis`` via all_to_all.

    Shapes: q/k/v ``[seq, heads, dim]`` sharded ``P(axis, None, None)``;
    requires ``heads % mesh.shape[axis] == 0`` and
    ``seq % mesh.shape[axis] == 0``. Returns the same shape/sharding as
    ``q``. Matches :func:`ring_attention` / :func:`reference_attention`.

    ``impl="flash"`` runs the local per-head full-sequence attention
    through the crossover dispatch (:func:`ops.flash_attention.
    best_attention`) — at long sequences (the regime Ulysses exists for)
    that is the Pallas kernel fwd AND bwd, never slower than the XLA path
    at any length. Extra ``attn_kwargs`` (``min_flash_seq``,
    ``interpret``, block sizes) pass through to the dispatch, which is
    how CI exercises the kernel branch off-TPU (interpret mode).
    """
    if impl not in ("xla", "flash"):
        raise ValueError(f"unknown ulysses impl {impl!r}")
    if impl == "xla" and attn_kwargs:
        raise ValueError("attn_kwargs only apply to impl='flash'")
    n_shards = int(mesh.shape[axis])
    seq, heads = int(q.shape[0]), int(q.shape[1])
    if heads % n_shards != 0:
        raise ValueError(
            f"ulysses needs heads ({heads}) divisible by mesh axis "
            f"{axis}={n_shards}; use ring_attention for fewer heads")
    if seq % n_shards != 0:
        raise ValueError(f"seq {seq} must divide over {n_shards} shards")
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    spec = P(axis, None, None)
    if impl == "flash":
        from .flash_attention import best_attention as _local_attn
    else:
        _local_attn = reference_attention

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def _ulysses(q_blk, k_blk, v_blk):
        # [seq/S, H, d] -> [seq, H/S, d]: gather the full sequence for a
        # slice of the heads
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0,
                                      tiled=True)

        qf, kf, vf = to_heads(q_blk), to_heads(k_blk), to_heads(v_blk)
        # the local per-head computation IS the oracle (xla impl) or the
        # crossover-dispatched kernel (flash impl); f32 accumulation inside
        out = _local_attn(qf, kf, vf, causal=causal, scale=scale,
                          **attn_kwargs)
        # [seq, H/S, d] -> [seq/S, H, d]
        return jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=1,
                                  tiled=True).astype(q_blk.dtype)

    return _ulysses(q, k, v)


def ulysses_prefill_attention(q, kc, vc, n_heads: int, offset, mesh,
                              axis: str = SEQ_AXIS) -> jax.Array:
    """All-to-all-resharded serving chunk attention, bit-exact vs the engine.

    The serving face of :func:`ulysses_attention`: ``q [C, D]`` chunk
    rows sharded ``P(axis, None)``, ``kc``/``vc`` ``[T, D]`` the slot's
    gathered paged view HEAD-sharded ``P(None, axis)`` — the paged
    pool's native layout, so the prefix K/V never reshards. One
    ``all_to_all`` turns the row shard of q into a head shard (full
    chunk rows, ``H/n`` whole heads per device — the contiguous
    ``D/n`` slice matches the pool shard by construction), the local
    computation is the engine's exact `_chunk_attention` math over the
    full ``T`` for those heads, and the reverse ``all_to_all`` restores
    row sharding. Per-head math is untouched by the resharding, hence
    bit-identical rows. Requires ``C % n == 0`` and ``n_heads % n == 0``
    (whole heads per device; ``offset`` is the traced global base row).
    """
    n = int(mesh.shape[axis])
    C, D = int(q.shape[0]), int(q.shape[1])
    T = int(kc.shape[0])
    if C % n != 0:
        raise ValueError(f"chunk rows {C} must divide over {n} shards")
    if n_heads % n != 0:
        raise ValueError(
            f"ulysses needs heads ({n_heads}) divisible by mesh axis "
            f"{axis}={n}; use ring_prefill_attention for fewer heads")
    hl = n_heads // n
    dh = D // n_heads

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(None, axis), P(None, axis), P()),
             out_specs=P(axis, None), check_vma=False)
    def _ulysses_sp(q_blk, k_blk, v_blk, off):
        # [C/n, D] -> [C, D/n]: full chunk rows for a whole-heads slice
        # (tiled concat lands peer p's rows at p*C/n — global row order)
        qf = jax.lax.all_to_all(q_blk, axis, split_axis=1, concat_axis=0,
                                tiled=True)
        rows = off + jnp.arange(C)
        out = _prefix_chunk_attn(qf.reshape(C, hl, dh),
                                 k_blk.reshape(T, hl, dh),
                                 v_blk.reshape(T, hl, dh), rows, dh)
        # [C, D/n] -> [C/n, D]
        return jax.lax.all_to_all(out.reshape(C, D // n), axis,
                                  split_axis=0, concat_axis=1,
                                  tiled=True).astype(q_blk.dtype)

    return _ulysses_sp(q, kc, vc, offset)
