"""Learned sparse attention over a paged latent pool (DeepSeek-V3.2's
DSA): an indexer scores every cached position for each query, each
query keeps its ``k`` best, and latent attention runs over those alone.

The indexer of a layer keeps one key a token, ``index_head_dim`` wide,
beside the layer's latent row in the same block pool under the same
block table (the model says where: ``models/glm5.py``). For a query
with ``Hi`` index heads ``q_j`` and head weights ``w_j`` the score of a
position with index key ``k`` is, in float32::

    I = sum_j w_j relu(q_j . k)

The pieces, each usable alone:

* :func:`index_scores`: that sum over given keys, in ``jax.numpy``;
* :func:`paged_index_scores`: the same for a one-token decode step,
  reading each slot's live index keys out of the pool in place (a
  Pallas kernel, op name :data:`DSA_KERNEL`), ``-inf`` at the positions
  a slot does not hold; :func:`step_index_scores` says where it applies;
* :func:`top_k_mask`: each row's ``k`` best positions as a mask, from an
  exact search for the ``k``-th largest score (a prefill chunk ANDs it
  into its causal mask): ``jax.lax.top_k``'s set, ties to the lower
  position as there;
* :func:`gather_rows` and :func:`sparse_latent_attention`: a decode
  step's selected latent rows, gathered by (block table, offset), and
  one latent query a slot against them.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _BF16_SUBLANES, _LANES, _on_tpu

# the indexer's kernel calls, by name in a device trace
DSA_KERNEL = "dsa_index_scores"
_NEG_INF = -1e30
# rows of a compute tile of the kernel: a tile of 512 index keys, 128
# values each, is 128 KB
_TILE_ROWS = 512


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I[c, t] = sum_j w[c, j] relu(q[c, j] . keys[t])``: queries ``q``
    [C, Hi, dI] and ``keys`` [T, dI] in one dtype (products accumulated
    in float32), head weights ``w`` [C, Hi] float32. Returns [C, T]
    float32; the weighted sum is elementwise, in float32."""
    s = jnp.einsum("cjd,td->cjt", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jnp.maximum(s, 0.0), axis=1)


def top_k_mask(scores: jax.Array, k: int) -> jax.Array:
    """``[..., T]`` bool: each row's ``k`` largest finite ``scores`` (all
    of its finite ones where it has ``k`` or fewer), the set that
    ``jax.lax.top_k`` picks: among scores equal to the ``k``-th largest
    the lowest positions. The ``k``-th largest is found exactly, one bit
    at a time from the top, over the float32 scores' order-preserving
    image in uint32 (32 counting passes), then one running count over
    the ties: in a prefill chunk's ``[512, 8704]`` against ``top_k`` and
    a scatter into a mask, PERF.md section 6 has both timed on
    the chip."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), u32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | u32(1 << 31))

    def narrow(i, kth):
        cand = kth | jnp.left_shift(u32(1), (31 - i).astype(u32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, narrow,
                            jnp.zeros(scores.shape[:-1], u32))[..., None]
    above = key > kth
    tied = key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    keep = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return keep & (scores > -jnp.inf)


def gather_rows(pool: jax.Array, sub: int, tables: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Sublayer ``sub``'s rows at ``positions`` [S, K] of each slot, whose
    blocks ``tables`` [S, M] name, out of the pool ``[subs, N, Bs, W]``:
    [S, K, W]. A position past a slot's table reads its last block."""
    n_sub, N, Bs, W = pool.shape
    block = jnp.take_along_axis(
        tables, jnp.minimum(positions // Bs, tables.shape[1] - 1), axis=1)
    flat = (sub * N + block) * Bs + positions % Bs
    return jnp.take(pool.reshape(n_sub * N * Bs, W), flat, axis=0,
                    mode="clip")


def sparse_latent_attention(q: jax.Array, rows: jax.Array, live: jax.Array,
                            *, scale: float, wv: int) -> jax.Array:
    """One query ``q`` [S, H, W] a slot, shared by its heads, against the
    slot's own rows ``rows`` [S, K, W] where ``live`` [S, K]: scores
    ``q . row * scale`` in float32, a masked softmax, the probabilities
    in the rows' dtype times the first ``wv`` columns. Returns [S, H,
    wv] float32."""
    s = jnp.einsum("shc,skc->shk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("shk,skc->shc", p.astype(rows.dtype), rows[..., :wv],
                      preferred_element_type=jnp.float32)


# -- the decode step's kernel -------------------------------------------------
def step_index_scores(pool_dtype, block_size: int, width: int,
                      index_width: int) -> Optional[Callable]:
    """:func:`paged_index_scores` where a one-token step can take it, on
    what the program can observe where it is jitted: a TPU, a bfloat16
    pool of blocks of whole ``(16, 128)`` tiles, index keys of whole
    128-lane tiles and blocks that make 512-row tiles. Else None: the
    step gathers the slots' index keys and scores them with
    :func:`index_scores`."""
    applies = (_on_tpu() and jnp.dtype(pool_dtype) == jnp.bfloat16
               and block_size > 0 and block_size % _BF16_SUBLANES == 0
               and _TILE_ROWS % block_size == 0
               and width % _LANES == 0 and index_width % _LANES == 0)
    return paged_index_scores if applies else None


def _index_kernel(lengths_ref, tables_ref, q_ref, w_ref, k_hbm, o_ref,
                  k_buf, sems, *, lane: int, tile_blocks: int,
                  table_width: int):
    s = pl.program_id(0)
    block_rows = k_hbm.shape[1]
    tile_rows = tile_blocks * block_rows
    width = k_buf.shape[-1]
    length = lengths_ref[s]
    blocks = (length + block_rows - 1) // block_rows
    n_tiles = (blocks + tile_blocks - 1) // tile_blocks
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def copies(tile, buf, act):
        """Start or wait the copies of this slot's live blocks of tile
        ``tile``: each block's ``width`` lanes from ``lane`` on."""
        first = tile * tile_blocks

        def one(j, carry):
            block = tables_ref[s * table_width + first + j]
            rows = pl.ds(pl.multiple_of(j * block_rows, block_rows),
                         block_rows)
            act(pltpu.make_async_copy(
                k_hbm.at[block, :, pl.ds(lane, width)],
                k_buf.at[buf, rows], sems.at[buf]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(blocks - first, tile_blocks), one,
                          0)

    @pl.when(blocks > 0)
    def _live_slot():
        copies(0, 0, lambda copy: copy.start())
        q = q_ref[...]                                          # [Hi, dI]
        w = w_ref[...][:, :1]                                   # [Hi, 1]

        def tile(t, carry):
            buf = t % 2

            @pl.when(t + 1 < n_tiles)
            def _next_tile():
                copies(t + 1, 1 - buf, lambda copy: copy.start())

            copies(t, buf, lambda copy: copy.wait())
            scores = jax.lax.dot_general(
                q, k_buf[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [Hi, R]
            score = jnp.sum(w * jnp.maximum(scores, 0.0), axis=0,
                            keepdims=True)                      # [1, R]
            # rows past the slot's length (a stale or unwritten copy)
            # never reach an output
            position = t * tile_rows + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile_rows), 1)
            o_ref[:, pl.ds(pl.multiple_of(t * tile_rows, tile_rows),
                           tile_rows)] = jnp.where(position < length, score,
                                                   -jnp.inf)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)


def paged_index_scores(q: jax.Array, w: jax.Array, pool: jax.Array,
                       tables: jax.Array, lengths: jax.Array, *, lane: int,
                       interpret: Optional[bool] = None,
                       name: str = DSA_KERNEL) -> jax.Array:
    """A one-token step's index scores against each slot's live keys
    where they lie.

    ``q`` [S, Hi, dI] in the pool's dtype, ``w`` [S, Hi] float32; ``pool``
    [B, Bs, W] (the engine's pool seen block-major, a bitcast) whose rows
    hold a slot's index keys at lanes ``lane .. lane + dI``; ``tables``
    [S, M] int32 ids into ``B`` (the keys' sublayer base already added;
    entries past a slot's live blocks are never read); ``lengths`` [S]
    int32, live positions a slot (0 for a dead lane). Returns [S, T]
    float32, ``T`` the tables' ``M x Bs`` rounded up to whole tiles: the
    score of :func:`index_scores` below a slot's length, ``-inf`` from
    it on. The grid runs over slots; inside one, the live blocks' key
    lanes are copied a tile at a time, two tile buffers, so that the
    next tile's copies are in flight while one is scored. ``name`` is
    the kernel's op name in a profile."""
    if interpret is None:
        interpret = not _on_tpu()
    return _paged_index_scores(q, w, pool, tables, lengths, lane=int(lane),
                               interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("lane", "interpret", "name"))
def _paged_index_scores(q, w, pool, tables, lengths, *, lane, interpret,
                        name):
    S, Hi, dI = q.shape
    block_rows = pool.shape[1]
    tile_blocks = max(1, _TILE_ROWS // block_rows)
    tile_rows = tile_blocks * block_rows
    M = tables.shape[1]
    T = -(-M * block_rows // tile_rows) * tile_rows
    lengths = jnp.minimum(lengths.astype(jnp.int32), M * block_rows)
    per_slot = lambda width: pl.BlockSpec((None, Hi, width),
                                          lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_index_kernel, lane=lane, tile_blocks=tile_blocks,
                          table_width=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[per_slot(dI), per_slot(_LANES),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, 1, T), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile_rows, dI), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((S, 1, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(lengths, tables.astype(jnp.int32).reshape(-1), q,
      jnp.broadcast_to(w.astype(jnp.float32)[:, :, None], (S, Hi, _LANES)),
      pool)
    return out[:, 0]
