"""Pallas TPU paged multi-query attention: the decode step reads the
live KV blocks where they lie.

The serving engine keeps K/V in a block pool ``[G, N + 1, Bs, W]``
(``G`` layers or sublayers, block 0 scratch) and names a slot's blocks in
a table ``[S, M]``. The view path (``models.transformer._paged_view``,
``models.longcat._view``) GATHERS every slot's ``M`` blocks into a
contiguous ``[S, M * Bs, W]`` array, live or not, and runs two products
over it. At 128 slots of 64 blocks with ~15% of the positions live that
gather was two thirds of the decode step (PERF.md, PR 26).

:func:`paged_mq_attention` is the same computation without the view: one
query ``[H, W]`` a slot, shared by all ``H`` heads against whole pool
rows (GPT-2: ``q`` spread block-diagonally over ``d_model``; LongCat:
the absorbed latent query), a masked float32 softmax over positions
``< lengths[s]``, probabilities rounded to the pool's dtype, times the
first ``wv`` columns of the value rows, float32 accumulation. The pools
stay in HBM; tables and lengths arrive by scalar prefetch and the kernel
issues its own block copies:

* the grid runs over SLOTS, never over blocks: inside a slot a loop
  bounded by ``ceil(lengths[s] / Bs)`` walks the live blocks only,
  ``tile_blocks`` of them to a compute tile, and a slot of length 0
  copies nothing (a grid step a block would cost more than the gather
  it replaces);
* two tile buffers: while one tile is multiplied, the copies of the
  next (this slot's next tile, or the first tile of the next LIVE slot,
  across the grid step) are in flight;
* scores, softmax (online, one pass) and accumulation in float32.

Dead rows never reach an output: a dead block is never copied, the dead
rows of a slot's last block are zeroed in VMEM before the value product,
and the buffers start at zero, so ``0 x garbage`` is never formed.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
_BF16_SUBLANES = 16
# blocks to a compute tile: 512 rows of 16-row blocks. On the v5e (PR 29,
# tools/paged_attention_probe.py) 4 / 8 / 16 / 32 blocks read 0.19 / 0.14 /
# 0.12 / 0.11 ms a layer at the GPT-2 cell's shapes and 0.91 / 0.60 / 0.47 /
# 0.40 at LongCat's: ~0.36 us a tile, whatever its rows
_TILE_BLOCKS = 32


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_applies(pool_dtype, block_size: int, width: int) -> bool:
    """Whether a one-token decode step over a paged pool takes the
    kernel: decided from what the program can observe where it is jitted
    (backend, pool dtype, block and row shape). A bfloat16 block of
    ``block_size % 16 == 0`` rows by ``width % 128 == 0`` lanes is whole
    ``(16, 128)`` tiles, so a block is one aligned copy."""
    return (_on_tpu() and jnp.dtype(pool_dtype) == jnp.bfloat16
            and block_size > 0 and block_size % _BF16_SUBLANES == 0
            and width % _LANES == 0)


def step_attention(pool_dtype, block_size: int,
                   width: int) -> Optional[Callable]:
    """What a model's program builder hands its one-token decode step as
    ``paged_attention``: :func:`paged_mq_attention` where
    :func:`kernel_applies`, else None (the step gathers the view). Every
    other program, backend, dtype and block shape keeps the view."""
    return (paged_mq_attention
            if kernel_applies(pool_dtype, block_size, width) else None)


def _kernel(lengths_ref, tables_ref, chain_ref, q_ref, *refs, scale: float,
            wv: int, tile_blocks: int, table_width: int, shared: bool):
    if shared:
        k_hbm, o_ref, k_buf, sems, parity = refs
        v_hbm, v_buf = k_hbm, k_buf
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, parity = refs
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    block_rows = k_hbm.shape[1]
    tile_rows = tile_blocks * block_rows
    heads = q_ref.shape[0]
    length = lengths_ref[s]

    def n_blocks(slot):
        return (lengths_ref[slot] + block_rows - 1) // block_rows

    def copies(slot, tile, buf, act):
        """Start or wait the copies of ``slot``'s live blocks of tile
        ``tile`` into buffer ``buf``."""
        first = tile * tile_blocks
        count = jnp.minimum(n_blocks(slot) - first, tile_blocks)

        def one(j, carry):
            block = tables_ref[slot * table_width + first + j]
            rows = pl.ds(pl.multiple_of(j * block_rows, block_rows),
                         block_rows)
            act(pltpu.make_async_copy(k_hbm.at[block], k_buf.at[buf, rows],
                                      sems.at[0, buf]))
            if not shared:
                act(pltpu.make_async_copy(v_hbm.at[block],
                                          v_buf.at[buf, rows],
                                          sems.at[1, buf]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    start = lambda copy: copy.start()
    wait = lambda copy: copy.wait()

    @pl.when(s == 0)
    def _first_step():
        parity[0] = 0
        # stale rows of a tile meet probability 0 in the value product:
        # they must be numbers
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(length == 0)
    def _dead_lane():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _live_slot():
        first_buf = parity[0]
        blocks = n_blocks(s)
        n_tiles = (blocks + tile_blocks - 1) // tile_blocks

        @pl.when(chain_ref[0] == s)
        def _nothing_in_flight():
            copies(s, 0, first_buf, start)

        q = q_ref[...]                                          # [H, W]

        def tile(t, carry):
            m, l, acc = carry
            buf = (first_buf + t) % 2
            last = t + 1 == n_tiles

            @pl.when(jnp.logical_not(last))
            def _next_tile():
                copies(s, t + 1, 1 - buf, start)

            @pl.when(last)
            def _next_slot():
                nxt = chain_ref[s + 1]

                @pl.when(nxt < n_slots)
                def _():
                    copies(nxt, 0, 1 - buf, start)

            copies(s, t, buf, wait)

            @pl.when(last)
            def _zero_dead_rows():
                # of the slot's last block: the only dead rows a copy
                # brings in
                rows = pl.ds(pl.multiple_of(
                    (blocks - 1 - t * tile_blocks) * block_rows,
                    block_rows), block_rows)
                live = (jax.lax.broadcasted_iota(
                    jnp.int32, (block_rows, v_buf.shape[-1]), 0)
                    < length - (blocks - 1) * block_rows)
                block = v_buf[buf, rows, :].astype(jnp.float32)
                v_buf[buf, rows, :] = jnp.where(live, block, 0.0).astype(
                    v_buf.dtype)

            k = k_buf[buf]                                      # [R, W]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [H, R]
            position = t * tile_rows + jax.lax.broadcasted_iota(
                jnp.int32, (heads, tile_rows), 1)
            scores = jnp.where(position < length, scores, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            v = v_buf[buf][:, :wv]                              # [R, wv]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)             # [H, wv]
            return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + pv)

        _, l, acc = jax.lax.fori_loop(
            0, n_tiles, tile,
            (jnp.full((heads, 1), _NEG_INF, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32),
             jnp.zeros((heads, wv), jnp.float32)))
        o_ref[...] = acc / l
        parity[0] = (first_buf + n_tiles) % 2


def paged_mq_attention(q: jax.Array, k_pool: jax.Array,
                       v_pool: Optional[jax.Array], tables: jax.Array,
                       lengths: jax.Array, *, scale: float, wv: int,
                       tile_blocks: int = _TILE_BLOCKS,
                       interpret: Optional[bool] = None) -> jax.Array:
    """One query a slot against the slot's live pool blocks.

    ``q`` [S, H, W] in the pools' dtype; ``k_pool`` / ``v_pool``
    [B, Bs, W] (the engine's pool seen block-major, a bitcast;
    ``v_pool`` None or ``k_pool`` itself: values are the first ``wv``
    columns of the key rows); ``tables`` [S, M] int32 ids into ``B``
    (the layer's base already added; entries past a slot's live blocks
    are never read); ``lengths`` [S] int32, live positions a slot
    (``pos + 1``; 0 for a dead lane, whose output is zeros). Scores are
    ``q . k * scale`` in float32. Returns [S, H, wv] float32.
    """
    if interpret is None:
        interpret = not _on_tpu()
    return _paged_mq_attention(
        q, k_pool, None if v_pool is k_pool else v_pool, tables, lengths,
        scale=float(scale), wv=wv, tile_blocks=tile_blocks,
        interpret=interpret)


# jitted so that a step's calls, one a layer with the same shapes, are
# traced and lowered to Mosaic ONCE (a call apiece cost ~0.3 s of every
# set-up, compile cache warm or not: PERF.md, PR 29)
@functools.partial(jax.jit, static_argnames=("scale", "wv", "tile_blocks",
                                             "interpret"))
def _paged_mq_attention(q, k_pool, v_pool, tables, lengths, *, scale, wv,
                        tile_blocks, interpret):
    S, H, W = q.shape
    shared = v_pool is None
    block_rows = k_pool.shape[1]
    tile_blocks = max(1, min(tile_blocks, tables.shape[1]))
    heads = -(-H // _BF16_SUBLANES) * _BF16_SUBLANES
    if heads != H:
        q = jnp.pad(q, ((0, 0), (0, heads - H), (0, 0)))
    # a length past the table would walk ids out of SMEM's bounds
    lengths = jnp.minimum(lengths.astype(jnp.int32),
                          tables.shape[1] * block_rows)
    # chain[0]: the first live slot; chain[s + 1]: the next live slot
    # after s (S where none): whose first tile to start copying while
    # slot s's last is multiplied
    slot = jnp.arange(S, dtype=jnp.int32)
    chain = jnp.concatenate([
        jax.lax.cummin(jnp.where(lengths > 0, slot, S), reverse=True),
        jnp.full((1,), S, jnp.int32)])
    tile = (2, tile_blocks * block_rows, W)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    per_slot = lambda width: pl.BlockSpec(
        (None, heads, width), lambda s, *_: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, wv=wv,
                          tile_blocks=tile_blocks,
                          table_width=tables.shape[1], shared=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[per_slot(W)] + [in_hbm] * (1 if shared else 2),
            out_specs=per_slot(wv),
            scratch_shapes=(
                [pltpu.VMEM(tile, k_pool.dtype)] * (1 if shared else 2)
                + [pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((1,), jnp.int32)])),
        out_shape=jax.ShapeDtypeStruct((S, heads, wv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_mq_attention",
    )(lengths, tables.astype(jnp.int32).reshape(-1), chain, q,
      *((k_pool,) if shared else (k_pool, v_pool)))
    return out[:, :H]
