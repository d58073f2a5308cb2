"""Embedding gather/scatter ops: the sparse-access substrate.

The reference's closest analog is the row-keyed MatrixTable traffic that
WordEmbedding drives (row Gets of touched vocab rows, row Adds of deltas —
``Applications/WordEmbedding/src/communicator.cpp:105,194`` in the Multiverso
reference). On TPU these are ``take`` gathers and ``segment_sum`` scatters
over an HBM-resident embedding matrix; XLA fuses the surrounding elementwise
work. Used by the word2vec model's hot loops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather rows: [vocab, dim] x [n] -> [n, dim]."""
    return jnp.take(table, ids, axis=0)


def scatter_add_rows(table: jax.Array, ids: jax.Array,
                     deltas: jax.Array) -> jax.Array:
    """Scatter-accumulate row deltas (duplicates sum, XLA scatter-add):
    the word2vec step's one table write. Every delta is rounded to the
    table's dtype and added on its own, so a bfloat16 row hit ``c`` times
    rounds ``c`` times: summing a row's deltas first is faster on the
    chip and another result (docs/W2V_KERNEL.md, 4 October 2026)."""
    return table.at[ids].add(deltas.astype(table.dtype))


def segment_mean_rows(values: jax.Array, segment_ids: jax.Array,
                      num_segments: int) -> jax.Array:
    """Mean-combine rows per segment (CBOW context averaging)."""
    sums = jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)
    counts = jax.ops.segment_sum(
        jnp.ones((values.shape[0],), values.dtype), segment_ids,
        num_segments=num_segments)
    return sums / jnp.maximum(counts, 1.0)[:, None]
