"""Price the word2vec row write on the chip, at the benchmark cell's shape.

The w2v cells spend most of their step in two scatter-adds of 65,536
float32 row updates into ``bf16[3000000, 300]`` tables (``PERF.md``
section 5). The candidate priced here writes every touched row once
instead (``row_runs`` / ``combine_rows`` / ``write_rows`` below): sort
the ids, sum the updates of equal ids in float32, add the distinct rows
to the table. It is faster and NOT what the trainer runs: in a bfloat16
table it is another result than the scatter-add, which rounds every
update into the row on its own, and the benchmark's reference refuses it
(docs/W2V_KERNEL.md, 4 October 2026). This tool reads what each piece
costs, as device time a pass (profiler, ``tools/xprof_util``), every
form run INSIDE a loop of one program, as the training step runs it
inside its scan (``looped``):

  plain            ``table.at[ids].add(bf16(upd))``: the incumbent;
                   ``plain.f32_table`` the same into a float32 table
                   (is it the packed bfloat16 rows that cost?)
  gather           ``jnp.take(table, ids)``: the read side, for scale;
                   ``gather.pallas_ring`` the same rows by DMA of their
                   enclosing tiles, DEPTH in flight
  sort             ``lax.sort((ids, slot))``
  keys             ``row_runs``: three sorts and a prefix sum;
                   ``keys.scatter`` the distinct ids by a scatter-max
  combine.*        the updates of equal ids applied to one row each:
                   ``round_each`` (a scatter whose combiner rounds every
                   sum to the table's dtype, which is what
                   ``combine_rows`` does to the gathered rows: the plain
                   scatter-add's arithmetic), ``table_dtype`` (a plain
                   scatter-add into a buffer of the table's dtype, which
                   XLA:TPU accumulates in float32), ``scatter_by_run``
                   (summed
                   in float32: the first design, which the benchmark's
                   reference refuses), ``gather_segsum`` (float32,
                   gathered into sorted order, then a sorted
                   segment-sum)
  fetch.chunked    the distinct rows gathered a chunk a gather
  write.*          the U distinct sorted rows into the table:
                   ``chunked_add`` (``write_rows``: as many scatter-adds
                   of ``WRITE_CHUNK_ROWS`` slots as the rows fill;
                   ``_1024`` / ``_4096`` other chunks), ``chunked_set``
                   (scatter-SETs of the rows' new values instead),
                   ``add`` (one scatter-add of all N slots, ids promised
                   sorted and unique; ``add_sorted`` / ``add_unique`` /
                   ``add_unpromised`` say which promise buys what),
                   ``gather_add_set`` (gather the rows, add, scatter-set
                   with both promises), ``pallas_serial`` and
                   ``pallas_ring`` / ``pallas_ring32`` (read-modify-write
                   of the enclosing 16-row tiles by DMA: one tile after
                   another, and 8 or 32 tiles in flight, which sorted
                   unique ids make race-free)
  whole            ``combine_rows`` + ``write_rows``: on a float32 table
                   (``whole.f32_table``) the scatter-add up to summation
                   order, on the cell's bfloat16 table another result
                   than ``plain`` (counted, element by element);
                   ``whole.each_rounded`` the form that was to keep the
                   bfloat16 arithmetic and does not (fetch the rows,
                   rounding combiner, scatter-SET)
  compact.*        the trainer's candidate compaction (163,840
                   candidates into 65,536 slots, two id arrays):
                   ``scatter`` each survivor to its prefix-count rank,
                   as the trainer did, ``sort`` by one sort, as
                   ``pack_survivors`` does now

for the cell's two update sizes (65,536 centres or contexts, 5,120
shared negatives) and, for ``plain`` and ``whole``, three sizes below,
down to where the two cross. Ids follow the cell's own law:
``benchmarks/gen.py`` (imported read-only) draws the corpus, the app's
``subsample_probs`` thins it at the configuration's 1e-3, negatives
follow unigram^0.75. Each form that returns a table is checked before it
is timed, against "sum in float32, round once, add" within an ulp
(``plain`` and ``whole.each_rounded`` round otherwise: their distance is
reported).

Mosaic refuses an HBM window below the tile (8 float32 rows, 16
bfloat16) and, of a 300-column table, ANY window (the loop holds the
table padded to 384 columns and a 300-wide slice is "not aligned to
tiling (128)"): ``subtile_rejected`` tries the first on every run and
fails if a later compiler accepts it; the tile kernels are priced on a
384-column stand-in.

Usage: python tools/w2v_kernel_probe.py  (on the chip; writes
``chiprun_out/w2v_kernel_probe.json``)
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

CELL = "w2v-news3m-d300"
DIM = 300             # read by tests/test_kernel_probe.py for its tables
CHUNK = 1024          # rows per grid step (divides both update sizes)
DEPTH = 8             # tile DMAs in flight in the ring kernel
SIZES = (65536, 5120)           # the cell's centres/contexts, negatives
THRESHOLD_SIZES = (8192, 16384, 32768)  # plain and whole only
CANDIDATES = 163840             # the cell's candidate slab (oversample 2.5)
# Index slots of one scatter of the combined write: XLA's scatter into the
# HBM table costs ~94 ns an index SLOT, a dropped one as much as a written
# one, so the distinct rows go in as many scatters of this many slots as
# they fill and the empty slots behind them are never issued
WRITE_CHUNK_ROWS = 2048


def tile_rows(dtype) -> int:
    """Rows of the smallest HBM window Mosaic will DMA: 8 sublanes of 32
    bits, so 8 float32 rows or 16 bfloat16 rows."""
    return 8 * (4 // np.dtype(dtype).itemsize)


# ---------------------------------------------------------------- kernels


def _tile_slice(pl, idx, tile):
    """The enclosing ``tile``-row slice of row ``idx``."""
    return pl.ds(pl.multiple_of((idx // tile) * tile, tile), tile)


def _gather_kernel(idx_ref, table_ref, out_ref, scratch, sems, *, tile):
    """Per-row gather via enclosing-tile DMA, a ring of tiles in flight
    (``sems``' length): the best per-row rate the kernel class reaches on
    its read side alone (a whole tile moved for every row)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, depth = out_ref.shape[0], sems.shape[0]

    def dma(i, slot):
        return pltpu.make_async_copy(
            table_ref.at[_tile_slice(pl, idx_ref[i], tile), :],
            scratch.at[pl.ds(pl.multiple_of(slot * tile, tile), tile), :],
            sems.at[slot])

    def retire(j, slot):
        dma(j, slot).wait()
        held = scratch[pl.ds(pl.multiple_of(slot * tile, tile), tile), :]
        hit = jax.lax.broadcasted_iota(jnp.int32, held.shape,
                                       0) == idx_ref[j] % tile
        # (a packed bfloat16 row cannot be read alone: pick it out of the
        # tile in float32)
        out_ref[pl.ds(j, 1), :] = jnp.sum(
            jnp.where(hit, held.astype(jnp.float32), 0.0), axis=0,
            keepdims=True)

    def body(i, _):
        slot = jax.lax.rem(i, depth)

        @pl.when(i >= depth)
        def _():
            retire(i - depth, slot)

        dma(i, slot).start()
        return 0

    jax.lax.fori_loop(0, chunk, body, 0)

    def drain(k, _):
        j = chunk - depth + k
        retire(j, jax.lax.rem(j, depth))
        return 0

    jax.lax.fori_loop(0, depth, drain, 0)


def pallas_gather(table, idx, interpret: bool = False):
    """``take(table, idx)`` as float32, every id inside the table."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim, tile = idx.shape[0], table.shape[1], tile_rows(table.dtype)
    return pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile),
        grid=n // CHUNK,
        in_specs=[
            pl.BlockSpec((CHUNK,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((CHUNK, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dim), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((DEPTH * tile, dim), table.dtype),
            pltpu.SemaphoreType.DMA((DEPTH,)),
        ],
        interpret=interpret,
    )(idx, table)


def _add_row(scratch, window, row, update):
    """``scratch[window][row] += update`` through the whole window in
    float32 (a packed bfloat16 row cannot be stored alone)."""
    import jax
    import jax.numpy as jnp

    held = scratch[window, :].astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, held.shape, 0) == row
    scratch[window, :] = jnp.where(hit, held + update, held).astype(
        scratch.dtype)


def _rmw_kernel(idx_ref, grad_ref, table_in_ref, table_out_ref,
                scratch, sem_in, sem_out, *, tile, num_rows):
    """Serial per-row read-modify-write via enclosing-tile DMA: safe for
    ANY ids, since row i's tile is back before row i+1 reads. An id past
    the table is skipped.

    Reads AND writes go through ``table_out_ref``: on TPU the aliased
    input is the same buffer, but interpret mode gives the input ref a
    stale snapshot — reading it would lose earlier duplicate-row
    updates (caught by tests/test_kernel_probe.py)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del table_in_ref     # aliased to table_out_ref; RMW uses one view

    def body(i, _):
        idx = idx_ref[i]

        @pl.when(idx < num_rows)
        def _():
            window = _tile_slice(pl, idx, tile)
            pltpu.make_async_copy(table_out_ref.at[window, :], scratch,
                                  sem_in).start()
            pltpu.make_async_copy(table_out_ref.at[window, :], scratch,
                                  sem_in).wait()
            _add_row(scratch, slice(None), idx % tile,
                     grad_ref[pl.ds(i, 1), :])
            pltpu.make_async_copy(scratch, table_out_ref.at[window, :],
                                  sem_out).start()
            pltpu.make_async_copy(scratch, table_out_ref.at[window, :],
                                  sem_out).wait()

        return 0

    jax.lax.fori_loop(0, grad_ref.shape[0], body, 0)


def _ring_kernel(idx_ref, grad_ref, table_in_ref, table_out_ref,
                 scratch, group, tiles, sem_in, sem_out, *, tile,
                 num_rows):
    """Read-modify-write of the tiles of SORTED, UNIQUE ids with a ring
    of tiles in flight (``sem_in``'s length; DEPTH below). Sorted ids put
    the rows of one tile side by side, so a chunk's rows fall into groups, one a tile; no two groups of a
    chunk share a tile, so their DMAs cannot race. A first scalar pass
    numbers the groups; the second walks the rows, waits for a group's
    tile at its first row, adds each row, sends the tile back after its
    last, and starts the fetch DEPTH groups ahead once the slot's last
    write is back. A chunk drains before the next begins, because the
    next chunk's first tile may be this one's last. Ids past the table
    (sorted behind every row) are skipped."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del table_in_ref
    chunk = grad_ref.shape[0]
    DEPTH = sem_in.shape[0]

    def number(i, carry):
        n_groups, last = carry
        idx = idx_ref[i]
        t = jnp.where(idx < num_rows, idx // tile, -1)
        new = (t != last) & (t >= 0)
        n_groups = n_groups + new.astype(jnp.int32)
        group[i] = jnp.where(t >= 0, n_groups - 1, -1)

        @pl.when(new)
        def _():
            tiles[n_groups - 1] = t

        return n_groups, jnp.where(t >= 0, t, last)

    n_groups, _ = jax.lax.fori_loop(0, chunk, number,
                                    (jnp.int32(0), jnp.int32(-1)))

    def window(g):
        return pl.ds(pl.multiple_of(tiles[g] * tile, tile), tile)

    def held(g):
        return scratch.at[pl.ds(pl.multiple_of(
            jax.lax.rem(g, DEPTH) * tile, tile), tile), :]

    def fetch(g):
        return pltpu.make_async_copy(table_out_ref.at[window(g), :],
                                     held(g),
                                     sem_in.at[jax.lax.rem(g, DEPTH)])

    def send(g):
        return pltpu.make_async_copy(held(g),
                                     table_out_ref.at[window(g), :],
                                     sem_out.at[jax.lax.rem(g, DEPTH)])

    def prime(g, _):
        @pl.when(g < n_groups)
        def _():
            fetch(g).start()

        return 0

    jax.lax.fori_loop(0, DEPTH, prime, 0)

    def body(i, _):
        g = group[i]
        nxt = jnp.where(i + 1 < chunk, group[jnp.minimum(i + 1, chunk - 1)],
                        -1)

        @pl.when(g >= 0)
        def _():
            prev = jnp.where(i > 0, group[jnp.maximum(i - 1, 0)], -1)

            @pl.when(prev != g)
            def _():
                fetch(g).wait()

            slot = jax.lax.rem(g, DEPTH)
            _add_row(scratch, pl.ds(pl.multiple_of(slot * tile, tile), tile),
                     idx_ref[i] % tile, grad_ref[pl.ds(i, 1), :])

            @pl.when(nxt != g)
            def _():
                send(g).start()

                # the slot of the group before: its tile is on its way
                # back since a whole group ago
                @pl.when(g >= 1)
                def _():
                    send(g - 1).wait()

                    @pl.when(g - 1 + DEPTH < n_groups)
                    def _():
                        fetch(g - 1 + DEPTH).start()

        return 0

    jax.lax.fori_loop(0, chunk, body, 0)

    @pl.when(n_groups > 0)
    def _():
        send(n_groups - 1).wait()


def _tile_rmw(kernel, scratch_rows, n_sems, table, idx, grads, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim, tile = idx.shape[0], table.shape[1], tile_rows(table.dtype)
    scratch = [pltpu.VMEM((scratch_rows * tile, dim), table.dtype)]
    if kernel is _ring_kernel:
        scratch += [pltpu.SMEM((CHUNK,), jnp.int32),
                    pltpu.SMEM((CHUNK,), jnp.int32)]
    return pl.pallas_call(
        functools.partial(kernel, tile=tile, num_rows=table.shape[0]),
        grid=n // CHUNK,
        in_specs=[
            pl.BlockSpec((CHUNK,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((CHUNK, dim), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(n_sems),
                                  pltpu.SemaphoreType.DMA(n_sems)],
        interpret=interpret,
    )(idx, grads.astype(jnp.float32), table)


def pallas_rmw(table, idx, grads, interpret: bool = False):
    """``table[idx] += grads`` one tile after another; any ids."""
    return _tile_rmw(_rmw_kernel, 1, (), table, idx, grads, interpret)


def pallas_ring_rmw(table, idx, grads, interpret: bool = False,
                    depth: int = 0):
    """``table[idx] += grads`` for SORTED, UNIQUE ``idx`` (ids past the
    table behind), ``depth`` (default DEPTH) tiles in flight."""
    depth = depth or DEPTH
    return _tile_rmw(_ring_kernel, depth, (depth,), table, idx, grads,
                     interpret)


def subtile_rejected(dtype="bfloat16") -> str:
    """Self-verifying form of the probe's oldest finding: attempt the
    ACTUAL per-row kernel — a (1, DIM) HBM row slice DMA — and return
    the compiler's rejection. If a future Mosaic release starts
    accepting sub-tile slices, this raises and the tile kernels' price
    in docs/W2V_KERNEL.md must be re-measured."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype)

    def kern(idx_ref, table_ref, out_ref, scratch, sem):
        def body(i, _):
            row = pl.ds(idx_ref[i], 1)           # sub-tile: 1 row
            pltpu.make_async_copy(table_ref.at[row, :], scratch,
                                  sem).start()
            pltpu.make_async_copy(table_ref.at[row, :], scratch,
                                  sem).wait()
            out_ref[pl.ds(i, 1), :] = scratch[:, :]
            return 0

        jax.lax.fori_loop(0, 8, body, 0)

    rows = tile_rows(dtype)
    call = pl.pallas_call(
        kern, grid=1,
        in_specs=[pl.BlockSpec((8,), lambda i: (0,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, DIM), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, DIM), dtype),
        scratch_shapes=[pltpu.VMEM((1, DIM), dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    try:
        np.asarray(call(jnp.zeros(8, jnp.int32),
                        jnp.zeros((64, DIM), dtype)).astype(jnp.float32))
    except Exception as exc:                     # expected: Mosaic reject
        # a rejection with ANY wording keeps the measured verdict valid;
        # only genuine ACCEPTANCE (the fall-through below) triggers the
        # re-measure alarm
        msg = str(exc)
        if "aligned to tiling" in msg:
            return f"rejected: slice must be aligned to tiling ({rows})"
        return ("rejected (unrecognized wording — still a reject): "
                + (msg.splitlines() or ["<no message>"])[-1][-200:])
    raise AssertionError(
        "Mosaic now ACCEPTS sub-tile HBM DMA slices — the per-row kernel "
        "class exists after all; re-measure docs/W2V_KERNEL.md's prices")


# ------------------------------------------------------------- the forms


def row_runs(ids, num_rows: int):
    """Which slots of an update share a row: ``(run_of_slot [N], uids
    [N'], written)``. Equal ids share a run; runs are numbered in
    ascending id order, so run ``r < written`` is ``uids[r]``; behind
    them ``uids`` holds ids past the table (``num_rows + slot``: the
    whole of it is sorted AND unique), up to ``N'``, ``N`` rounded up to
    whole write chunks. A slot whose id lies outside ``[0, num_rows)`` is
    offered nothing: such slots share one last run behind every row.

    Three sorts of ``N`` keys (a TPU sorts 65,536 keys with a payload in
    ~0.05 ms, where a scatter of as many scalars takes ~0.4): by id, for
    the runs of equal ids; back by slot, for every slot's run number; of
    the run starts' ids, for the distinct ids in front. None needs to be
    stable, and a stable 65,536-key sort takes twice as long to compile
    (13 s against 6 for a described v5e)."""
    import jax
    import jax.numpy as jnp

    n = ids.shape[0]
    live = (ids >= 0) & (ids < num_rows)
    slot = jnp.arange(n, dtype=jnp.int32)
    keys, perm = jax.lax.sort((jnp.where(live, ids, num_rows), slot),
                              num_keys=1, is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    run = jnp.cumsum(first.astype(jnp.int32)) - 1
    _, run_of_slot = jax.lax.sort((perm, run), num_keys=1, is_stable=False)
    written = run[-1] + 1 - (keys[-1] == num_rows).astype(jnp.int32)
    uids = jax.lax.sort(jnp.where(first, keys, num_rows), is_stable=False)
    uids = jnp.where(slot < written, uids, num_rows + slot)
    pad = -n % min(WRITE_CHUNK_ROWS, n)
    if pad:
        uids = jnp.concatenate(
            [uids, num_rows + n + jnp.arange(pad, dtype=jnp.int32)])
    return run_of_slot, uids, written


def combine_rows(ids, updates, num_rows: int):
    """Sum the ``updates [N, D]`` of equal ``ids [N]`` in float32: ``(uids
    [N'], sums [N', D] float32, written)`` (:func:`row_runs`): the first
    ``written`` slots hold the distinct ids in ascending order with the
    sum of their updates, the slots behind them ids past the table and
    zeros. No ``[num_rows, D]`` temporary is built: the sums take ``[N',
    D]``, which the compiler keeps in fast memory, where a slot's
    read-modify-write costs a quarter of HBM's."""
    import jax.numpy as jnp

    run_of_slot, uids, written = row_runs(ids, num_rows)
    live = (ids >= 0) & (ids < num_rows)
    sums = jnp.zeros((uids.shape[0],) + updates.shape[1:], jnp.float32)
    sums = sums.at[run_of_slot].add(
        jnp.where(live[:, None], updates.astype(jnp.float32), 0.0))
    return uids, sums, written


def write_rows(table, uids, sums, written, chunk: int = WRITE_CHUNK_ROWS):
    """Add the first ``written`` of ``combine_rows``'s rows to ``table``,
    ``chunk`` slots a scatter, in as many scatters as they fill (a trip
    count read on the device). The ids are unique, which the scatter is
    told; that they are sorted it is NOT told: XLA:TPU then sweeps the
    whole operand (~10 ms for a 2.3 GB table, whatever the update's size;
    ``write.add_sorted``)."""
    import jax

    chunk = min(chunk, uids.shape[0])

    def write(i, table):
        return table.at[
            jax.lax.dynamic_slice_in_dim(uids, i * chunk, chunk)].add(
            jax.lax.dynamic_slice_in_dim(sums, i * chunk, chunk)
            .astype(table.dtype), mode="drop", unique_indices=True)

    return jax.lax.fori_loop(0, (written + chunk - 1) // chunk, write, table)


def cell_ids(seed: int, vocab: int, total_words: float, sample: float,
             sizes, negatives_size: int = 5120) -> dict:
    """For each size ``n``, ``n`` update row ids as a step of the cell
    offers them: corpus words that survive subsampling (centres and
    contexts share that law), and for the cell's negatives size
    unigram^0.75 draws."""
    from benchmarks import gen
    from multiverso_tpu.apps.wordembedding import subsample_probs

    counts = gen.w2v_counts(vocab, total_words)
    discard = subsample_probs(counts, sample)
    rng = np.random.default_rng(seed)
    words, _ = gen.w2v_corpus(seed, 4 * max(sizes), vocab, 1000)
    words = np.asarray(words)
    words = words[rng.random(words.shape[0]) >= discard[words]]
    p_neg = counts ** 0.75
    p_neg /= p_neg.sum()
    out = {}
    for n in sizes:
        if n == negatives_size:
            out[n] = rng.choice(vocab, size=n, p=p_neg).astype(np.int32)
        else:
            out[n] = words[:n].astype(np.int32)
            assert out[n].shape[0] == n, "too few survivors"
    return out


def expected_sums(ids, upd):
    """``(distinct ids, their updates summed in float64)`` on the host."""
    uniq, inv = np.unique(ids, return_inverse=True)
    sums = np.zeros((uniq.shape[0], upd.shape[1]), np.float64)
    np.add.at(sums, inv, upd.astype(np.float64))
    return uniq, sums


def forms(num_rows: int):
    """name -> (function, argument names, whether it returns the new
    table; the others return any one array)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import embedding

    def plain(table, ids, upd):
        return embedding.scatter_add_rows(table, ids, upd)

    def gather(table, ids):
        return jnp.take(table, ids, axis=0)

    def gather_pallas_ring(table, ids):
        return pallas_gather(table, ids)

    def sort(ids):
        return jax.lax.sort((ids, jnp.arange(ids.shape[0],
                                             dtype=jnp.int32)),
                            num_keys=1)[1]

    def keys(ids):
        run_of_slot, uids, _ = row_runs(ids, num_rows)
        return jnp.concatenate([run_of_slot, uids])

    def keys_scatter(ids):
        n = ids.shape[0]
        slot = jnp.arange(n, dtype=jnp.int32)
        srt, perm = jax.lax.sort((ids, slot), num_keys=1)
        first = jnp.concatenate([jnp.ones((1,), bool), srt[1:] != srt[:-1]])
        run = jnp.cumsum(first.astype(jnp.int32)) - 1
        return perm + jnp.zeros((n,), jnp.int32).at[run].max(srt)

    def combine_scatter_by_run(run_of_slot, upd):
        return jnp.zeros(upd.shape, jnp.float32).at[run_of_slot].add(upd)

    def combine_table_dtype(table, run_of_slot, upd):
        """A scatter-add into a buffer of the table's dtype: XLA:TPU
        accumulates it in float32 all the same."""
        return jnp.zeros(upd.shape, table.dtype).at[run_of_slot].add(
            upd.astype(table.dtype))

    def round_each_into(rows, dtype, run_of_slot, upd):
        """A scatter into the float32 ``rows`` whose COMBINER rounds every
        sum to ``dtype``: the HBM scatter-add's arithmetic on paper;
        XLA:TPU adds equal indices up in float32 all the same."""
        def round_add(x, y):
            return (x + y).astype(dtype).astype(jnp.float32)

        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        combiner = jax.make_jaxpr(round_add)(scalar, scalar)
        return jax.lax.scatter_add_p.bind(
            rows, run_of_slot[:, None],
            upd.astype(dtype).astype(jnp.float32),
            update_jaxpr=combiner.jaxpr,
            update_consts=tuple(combiner.consts),
            dimension_numbers=jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0,)),
            indices_are_sorted=False, unique_indices=False,
            mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

    def combine_round_each(table, run_of_slot, upd):
        return round_each_into(jnp.zeros(upd.shape, jnp.float32),
                               table.dtype, run_of_slot, upd)

    def fetch_chunked(table, uids, written):
        chunk = WRITE_CHUNK_ROWS

        def fetch(i, rows):
            at = jax.lax.dynamic_slice_in_dim(uids, i * chunk, chunk)
            return jax.lax.dynamic_update_slice_in_dim(
                rows, jnp.take(table, at, axis=0, mode="fill", fill_value=0),
                i * chunk, axis=0)

        return jax.lax.fori_loop(
            0, (written + chunk - 1) // chunk, fetch,
            jnp.zeros((uids.shape[0],) + table.shape[1:], table.dtype))

    def combine_gather_segsum(run_of_slot, upd):
        run, perm = jax.lax.sort(
            (run_of_slot, jnp.arange(upd.shape[0], dtype=jnp.int32)),
            num_keys=1)
        return jax.ops.segment_sum(jnp.take(upd, perm, axis=0), run,
                                   num_segments=upd.shape[0],
                                   indices_are_sorted=True)

    def write_add(table, uids, sums, **promises):
        return table.at[uids].add(sums.astype(table.dtype), mode="drop",
                                  **promises)

    def write_gather_add_set(table, uids, sums):
        rows = jnp.take(table, uids, axis=0, mode="fill", fill_value=0,
                        indices_are_sorted=True, unique_indices=True)
        return table.at[uids].set(rows + sums.astype(table.dtype),
                                  mode="drop", indices_are_sorted=True,
                                  unique_indices=True)

    def whole(table, ids, upd):
        uids, sums, written = combine_rows(ids, upd, num_rows)
        return write_rows(table, uids, sums, written)

    def whole_each_rounded(table, ids, upd):
        """The form that was to keep a bfloat16 table's arithmetic: fetch
        the touched rows, add every update to its row's copy through the
        rounding combiner, scatter-SET the rows."""
        run_of_slot, uids, written = row_runs(ids, num_rows)
        rows = round_each_into(
            fetch_chunked(table, uids, written).astype(jnp.float32),
            table.dtype, run_of_slot, upd)
        return write_set(table, uids, rows.astype(table.dtype), written)

    def write_set(table, uids, rows, written):
        chunk = min(WRITE_CHUNK_ROWS, uids.shape[0])

        def write(i, table):
            return table.at[
                jax.lax.dynamic_slice_in_dim(uids, i * chunk, chunk)].set(
                jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk),
                mode="drop", unique_indices=True)

        return jax.lax.fori_loop(0, (written + chunk - 1) // chunk, write,
                                 table)

    def write_chunked_set(table, uids, sums, written):
        """Scatter-SETs of the rows' new values (here the gathered rows
        plus the rounded sums)."""
        rows = jnp.take(table, uids, axis=0, mode="fill", fill_value=0)
        return write_set(table, uids, rows + sums.astype(table.dtype),
                         written)

    def compact_scatter(ok, a, b):
        out = ok.shape[0] * SIZES[0] // CANDIDATES
        rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
        dest = jnp.where(ok & (rank < out), rank, out)
        return sum(jnp.zeros((out,), x.dtype).at[dest].set(x, mode="drop")
                   for x in (a, b))

    def compact_sort(ok, a, b):
        out = ok.shape[0] * SIZES[0] // CANDIDATES
        _, a, b = jax.lax.sort(((~ok).astype(jnp.int32), a, b), num_keys=1,
                               is_stable=True)
        return a[:out] + b[:out]

    both = dict(indices_are_sorted=True, unique_indices=True)
    written = ("table", "uids", "sums")
    return {
        "plain": (plain, ("table", "ids", "upd"), True),
        "plain.f32_table": (plain, ("table", "ids", "upd"), True),
        "gather": (gather, ("table", "ids"), False),
        "gather.pallas_ring": (gather_pallas_ring, ("table", "ids"), False),
        "sort": (sort, ("ids",), False),
        "keys": (keys, ("ids",), False),
        "keys.scatter": (keys_scatter, ("ids",), False),
        "combine.scatter_by_run": (combine_scatter_by_run,
                                   ("run_of_slot", "upd"), False),
        "combine.gather_segsum": (combine_gather_segsum,
                                  ("run_of_slot", "upd"), False),
        "combine.table_dtype": (combine_table_dtype,
                                ("table", "run_of_slot", "upd"), False),
        "combine.round_each": (combine_round_each,
                               ("table", "run_of_slot", "upd"), False),
        "fetch.chunked": (fetch_chunked, ("table", "uids", "written"),
                          False),
        "write.chunked_set": (write_chunked_set, written + ("written",),
                              True),
        "write.chunked_add": (write_rows, written + ("written",), True),
        "write.chunked_add_1024": (functools.partial(write_rows, chunk=1024),
                                   written + ("written",), True),
        "write.chunked_add_4096": (functools.partial(write_rows, chunk=4096),
                                   written + ("written",), True),
        "whole.each_rounded": (whole_each_rounded, ("table", "ids", "upd"),
                               True),
        "write.add": (functools.partial(write_add, **both), written, True),
        "write.add_sorted": (
            functools.partial(write_add, indices_are_sorted=True), written,
            True),
        "write.add_unique": (
            functools.partial(write_add, unique_indices=True), written,
            True),
        "write.add_unpromised": (write_add, written, True),
        "write.gather_add_set": (write_gather_add_set, written, True),
        "write.pallas_serial": (pallas_rmw, written, True),
        "write.pallas_ring": (pallas_ring_rmw, written, True),
        "write.pallas_ring32": (functools.partial(pallas_ring_rmw, depth=32),
                                written, True),
        "whole": (whole, ("table", "ids", "upd"), True),
        "whole.f32_table": (whole, ("table", "ids", "upd"), True),
        "compact.scatter": (compact_scatter, ("ok", "cand_a", "cand_b"),
                            False),
        "compact.sort": (compact_sort, ("ok", "cand_a", "cand_b"), False),
    }


# ------------------------------------------------------------ measurement


def looped(fn, argnames, returns_table: bool, num_rows: int,
           program: str):
    """``fn`` run ``k`` times inside ONE program, as the training step
    runs it inside its scan: the table is carried in the layout the
    compiler picks for the loop (row-major, two bfloat16 rows a
    sublane; the layout change of the whole table at the program's
    edges is paid once, outside the loop), and nothing is hoisted,
    because every pass shifts its ids by the pass number plus a scalar
    the compiler cannot foresee. A shift keeps which ids are equal,
    sorted ids sorted and unique ids unique. ``k`` is an operand, so
    two trip counts share one compilation and their difference prices
    a pass."""
    import jax
    import jax.numpy as jnp

    names = [a for a in argnames if a != "table"]

    def shifted(name, value, i, dep):
        if name == "ids":
            return (value + i * 7919 + dep) % num_rows
        if name == "uids":
            return value + i + dep
        if name in ("upd", "sums"):
            return value * (1.0 + i.astype(jnp.float32))
        if name == "ok":        # another ~45% of the candidates a pass
            return (value + i + dep) % 100 < 45
        return value            # runs, the row count, candidate ids

    def program_fn(k, table, *rest):
        def body(i, carry):
            table, dep = carry
            args = {a: shifted(a, v, i, dep) for a, v in zip(names, rest)}
            args["table"] = table
            out = fn(*(args[a] for a in argnames))
            if returns_table:
                return out, dep
            # the whole result is read, so none of it can be left out
            seen = jnp.sum(out.astype(jnp.float32))
            return table, dep + jnp.where(seen == 12345.678, 1, 0)

        return jax.lax.fori_loop(0, k, body, (table, jnp.int32(0)))

    program_fn.__name__ = program
    return program_fn


def _check(name, got_table, base_rows, uniq, sums, room: float = 1.0):
    """``got_table`` against "sum, round once, add" on the touched rows,
    with ``room`` ulps of the table's dtype for the summation order."""
    import jax.numpy as jnp

    got = np.asarray(jnp.take(got_table, jnp.asarray(uniq), axis=0)
                     .astype(jnp.float32))
    dt = got_table.dtype
    sums32 = sums.astype(np.float32)
    want = np.asarray((jnp.asarray(base_rows).astype(dt) + jnp.asarray(
        sums32).astype(dt)).astype(jnp.float32))
    ulp = (np.abs(want) + np.abs(sums32) + 1e-12) * (
        2.0 ** -7 if dt == jnp.bfloat16 else 2.0 ** -20)
    worst = float(np.max(np.abs(got - want) / ulp))
    assert worst <= room, f"{name}: {worst:.2f} ulp off the reference"
    return round(worst, 3)


def check_form(name, fn, argnames, base, args, ids_np, upd_np, uniq):
    """Run a table-returning form once, undonated, and hold it to "sum in
    float32, round once, add": ``(worst distance in ulps, elements off
    ``plain`` or None)``. Forms that round every update on its own, and
    float32 tables (whose ulp is finer than the compiler's accumulate in
    fast memory), have their distance reported, not bounded."""
    import jax
    import jax.numpy as jnp

    _, sums_np = expected_sums(ids_np, upd_np)
    base_rows = np.asarray(jnp.take(base, jnp.asarray(uniq), axis=0)
                           .astype(jnp.float32))
    got = jax.jit(fn)(base, *(args[a] for a in argnames[1:]))
    free = (name.startswith(("plain", "whole.each"))
            or base.dtype == jnp.float32)
    worst = _check(name, got, base_rows, uniq, sums_np,
                   room=np.inf if free else 1.0)
    off = None
    if name.startswith("whole") and base.dtype != jnp.float32:
        # (a third float32 table would not fit the chip)
        off = int(jnp.sum(got != jax.jit(forms(base.shape[0])["plain"][0])(
            base, args["ids"], args["upd"])))
    return worst, off


def time_form(fn, argnames, returns_table, base, args, program, trips):
    """Device ms of ``trips[0]`` and of ``trips[1]`` passes of one
    compilation of ``looped(fn)``, the table donated and handed on."""
    import jax
    import jax.numpy as jnp

    from tools.xprof_util import trace_device_ms

    step = jax.jit(looped(fn, argnames, returns_table, base.shape[0],
                          program), donate_argnums=1)
    holder = [base + 0]
    rest = [args[a] for a in argnames if a != "table"]

    def run(k):
        holder[0], dep = step(jnp.int32(k), holder[0], *rest)
        return dep

    jax.block_until_ready(run(trips[0]))
    return [trace_device_ms(functools.partial(run, k), iters=3,
                            program=program) for k in trips]


def inputs(n, ids_np, V, rng):
    """The arguments every form of one size shares."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids_np)
    run_of_slot, uids, written = jax.jit(
        functools.partial(row_runs, num_rows=V))(ids)
    m = n * CANDIDATES // SIZES[0]
    return {"ids": ids, "run_of_slot": run_of_slot, "uids": uids,
            "written": written,
            "ok": jnp.asarray(rng.integers(0, 100, m), jnp.int32),
            "cand_a": jnp.asarray(rng.integers(0, V, m), jnp.int32),
            "cand_b": jnp.asarray(rng.integers(0, V, m), jnp.int32)}


def measure(sizes, threshold_sizes, only=(), trips=(2, 12)) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks import harness

    cfg = harness.load_json(harness.ROOT, "benchmarks", "configs",
                            CELL + ".json")
    V, D = cfg["vocab_size"], cfg["embedding_size"]
    dtype = jnp.dtype(cfg["table_dtype"])
    all_ids = cell_ids(11, V, cfg["total_words"], cfg["sample"],
                       tuple(sizes) + tuple(threshold_sizes))
    rng = np.random.default_rng(5)
    padded = -(-D // 128) * 128     # the tile kernels' stand-in width

    def table_of(name):
        """(columns, dtype) of the table a form is run on."""
        if "pallas" in name:
            return padded, dtype
        return D, (jnp.dtype(jnp.float32) if name.endswith("f32_table")
                   else dtype)

    @functools.cache
    def make_table(width, dt):
        return jax.jit(lambda k: ((jax.random.uniform(k, (V, width)) - 0.5)
                                  / D).astype(dt))(jax.random.PRNGKey(3))

    table_forms = forms(V)
    sums_of = jax.jit(table_forms["combine.scatter_by_run"][0])
    out = {"device": jax.devices()[0].device_kind, "vocab": V, "dim": D,
           "table_dtype": str(dtype), "depth": DEPTH, "chunk": CHUNK,
           "write_chunk_rows": WRITE_CHUNK_ROWS,
           "trips": list(trips),
           "subtile_dma": subtile_rejected(dtype), "sizes": {}}
    print(f"sub-tile row DMA: {out['subtile_dma']}", flush=True)
    tag = 0
    for n in tuple(sizes) + tuple(threshold_sizes):
        ids_np = all_ids[n]
        uniq = np.unique(ids_np)
        rows = {"distinct_rows": int(uniq.shape[0]),
                "distinct_tiles": int(np.unique(
                    uniq // tile_rows(dtype)).shape[0]),
                "hottest_row": int(np.bincount(ids_np).max())}
        print(f"N={n}: {rows}", flush=True)
        shared = inputs(n, ids_np, V, rng)
        assert int(shared["written"]) == uniq.shape[0]
        names = (table_forms if n in sizes else
                 ("plain", "whole", "plain.f32_table", "whole.f32_table"))
        for name in names:
            if only and not any(name.startswith(o) for o in only):
                continue
            fn, argnames, returns_table = table_forms[name]
            width, dt = table_of(name)
            upd_np = (rng.standard_normal((n, width)) * 1e-3).astype(
                np.float32)
            upd = jnp.asarray(upd_np)
            sums = jnp.pad(sums_of(shared["run_of_slot"], upd),
                           ((0, shared["uids"].shape[0] - n), (0, 0)))
            args = dict(shared, upd=upd, sums=sums)
            tag += 1
            program = f"v{tag:02d}_" + name.replace(".", "_")
            try:
                base = make_table(width, dt)
                worst = off = None
                if returns_table:
                    worst, off = check_form(name, fn, argnames, base, args,
                                            ids_np, upd_np, uniq)
                ms = time_form(fn, argnames, returns_table, base, args,
                               program, trips)
            except Exception as exc:       # a form the compiler refuses
                rows[name] = {"failed": f"{type(exc).__name__}: "
                              + str(exc).strip().splitlines()[-1][-300:]}
                print(f"  {name:24s} FAILED {rows[name]['failed']}",
                      flush=True)
                continue
            a_pass = (ms[1] - ms[0]) / (trips[1] - trips[0])
            per = (uniq.shape[0] if name.startswith("write")
                   else args["ok"].shape[0] if name.startswith("compact")
                   else n)
            rows[name] = {"device_ms": round(a_pass, 4),
                          "ns_per_row": round(a_pass * 1e6 / per, 1),
                          "rows": int(per), "ulp_off": worst,
                          "program_ms": [round(x, 4) for x in ms],
                          "table": f"{dt}[{V},{width}]"}
            if off is not None:
                rows[name]["elements_off_plain"] = off
                rows[name]["touched_elements"] = int(uniq.shape[0] * width)
            print(f"  {name:24s} {a_pass:9.4f} ms a pass  "
                  f"{rows[name]['ns_per_row']:8.1f} ns a row of {per}"
                  f"   (programs {ms[0]:.3f} / {ms[1]:.3f} ms)"
                  + (f"   {off} elements off plain" if off is not None
                     else ""), flush=True)
        out["sizes"][str(n)] = rows
        make_table.cache_clear()        # one table at a time on the chip
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sizes", default=",".join(map(str, SIZES)))
    ap.add_argument("--threshold-sizes",
                    default=",".join(map(str, THRESHOLD_SIZES)))
    ap.add_argument("--forms", default="",
                    help="only the forms whose names start so (a,b,...)")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("w2v_kernel_probe: prices are device times; "
                         "run it on the chip")

    def ints(s):
        return tuple(int(x) for x in s.split(",") if x)

    out = measure(ints(args.sizes), ints(args.threshold_sizes),
                  only=tuple(f for f in args.forms.split(",") if f))
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_REPO, "chiprun_out", "w2v_kernel_probe.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
