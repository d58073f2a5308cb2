"""Transformer language model: the parallelism-showcase model family.

The reference predates transformers (SURVEY §5.7) — its model families are
word2vec and logistic regression, both reproduced in this package. This
module is the framework's forward-looking flagship: a decoder-only LM whose
training step composes the mesh axes the framework provides:

* **dp** — the batch shards over the ``worker`` axis; the mean-loss gradient
  becomes a ``psum`` over ICI (exactly the sync-PS contract,
  ``parallel/sync_step.py``);
* **tp** — attention/FFN weights shard over the ``server`` axis
  (Megatron-style column/row splits expressed as ``NamedSharding``s; XLA
  inserts the all-gathers/reduce-scatters);
* layers are **stacked** on a leading dim and applied with ``lax.scan`` —
  the same stacked layout ``parallel/pipeline.py`` consumes for pipeline
  parallelism over a ``stage`` axis;
* long-context attention is pluggable: the default local (full-sequence)
  attention shares :func:`ops.reference_attention`'s math; for sequence
  parallelism use :func:`ops.ring_attention` / :func:`ops.ulysses_attention`
  over a ``seq`` axis mesh.

Pre-LN, learned positions, tied input/output embeddings, SGD-with-momentum
update inline in the jitted step (params never leave HBM; buffers donated).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from ..log import Log
from ..ops import paged_attention as paged_kernel
from ..ops.ring_attention import ring_prefill_attention
from ..ops.ulysses import ulysses_prefill_attention
from ..topology import SERVER_AXIS, WORKER_AXIS


@dataclass
class TransformerConfig:
    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 256
    dtype: Any = jnp.float32
    learning_rate: float = 0.1
    momentum: float = 0.9
    seed: int = 0
    # scan over the layer stack instead of unrolling: cheaper compiles
    # for very deep models, ~30% slower steps (see forward())
    scan_layers: bool = False
    # attention implementation: "reference" (jnp, XLA-fused), "flash"
    # (crossover dispatch — Pallas kernel at/above the measured ~1.5k-seq
    # win threshold, XLA below; never slower than reference), or
    # "flash_force" (always the Pallas kernel, fwd+bwd;
    # ops/flash_attention.py — runs in interpret mode off-TPU, so tests
    # stay hermetic)
    attention: str = "reference"


def init_params(cfg: TransformerConfig,
                rng: Optional[np.random.Generator] = None) -> Dict[str, Any]:
    """Random parameter pytree; per-layer weights stacked on dim 0."""
    rng = rng or np.random.default_rng(cfg.seed)
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    s = 1.0 / np.sqrt(D)
    sf = 1.0 / np.sqrt(F)

    def mk(shape, scale):
        return jnp.asarray(rng.standard_normal(shape) * scale, cfg.dtype)

    return {
        "embed": mk((cfg.vocab_size, D), s),
        "pos": mk((cfg.max_seq, D), 0.02),
        "layers": {
            "ln1_g": jnp.ones((L, D), cfg.dtype),
            "ln2_g": jnp.ones((L, D), cfg.dtype),
            # separate Q/K/V projections: a fused [D, 3D] column-sharded
            # weight would put the Q/K/V split boundaries mid-shard for tp
            # sizes not divisible by 3, forcing a reshard every layer
            "w_q": mk((L, D, D), s),
            "w_k": mk((L, D, D), s),
            "w_v": mk((L, D, D), s),
            "w_o": mk((L, D, D), s),
            "w_ff1": mk((L, D, F), s),
            "w_ff2": mk((L, F, D), sf),
        },
        "ln_f_g": jnp.ones((D,), cfg.dtype),
    }


def param_shardings(cfg: TransformerConfig, mesh,
                    tp_axis: str = SERVER_AXIS) -> Dict[str, Any]:
    """Tensor-parallel layout over ``tp_axis``.

    Column-parallel ``w_q``/``w_k``/``w_v``/``w_ff1`` (output dim sharded),
    row-parallel ``w_o``/``w_ff2`` (input dim sharded) — XLA propagates
    these into the Megatron collective pattern. Embeddings shard by row like
    parameter tables; norms replicate.
    """
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    return {
        "embed": ns(tp_axis, None),
        "pos": ns(),
        "layers": {
            "ln1_g": ns(),
            "ln2_g": ns(),
            "w_q": ns(None, None, tp_axis),
            "w_k": ns(None, None, tp_axis),
            "w_v": ns(None, None, tp_axis),
            "w_o": ns(None, tp_axis, None),
            "w_ff1": ns(None, None, tp_axis),
            "w_ff2": ns(None, tp_axis, None),
        },
        "ln_f_g": ns(),
    }


def _rmsnorm(x, g):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                 keepdims=True) + 1e-6).astype(x.dtype) * g


def _attention(q, k, v, n_heads: int, impl: str = "reference"):
    """Causal multi-head attention, [B, T, D] in/out.

    ``impl="reference"``: :func:`ops.reference_attention` vmapped over
    batch — one causal-attention implementation shared by the model, the
    sequence-parallel ops, and the tests. ``impl="flash"``: crossover
    dispatch (:func:`ops.flash_attention.best_attention`) — the Pallas
    flash kernel at/above the measured ~1.5k-seq win threshold, the
    XLA-fused reference below it, so picking "flash" can never slow a
    model down. ``impl="flash_force"`` pins the Pallas kernel
    (online-softmax tiles in VMEM, Pallas fwd+bwd via custom VJP).
    """
    B, T, D = q.shape
    dh = D // n_heads
    split = lambda x: x.reshape(B, T, n_heads, dh)
    if impl == "flash":
        from ..ops.flash_attention import best_attention as fn

        if B * n_heads >= 64:
            # many-program calls amortise the kernel's launch and epilogue
            # over B x heads programs, and the surrounding model denies
            # XLA the fusions that make its attention cheap standalone:
            # measured in-model (12 layers, ~8k tok/step, d_model 768,
            # 96-192 programs), flash TIES reference at seq 512 and wins
            # 1.5x/2x at 1024/2048 — so the crossover drops to 512 there.
            # Few-program calls (the standalone 8-program sweep ran
            # 0.44-0.63x below seq 1536, docs/TPU_VALIDATE.json) keep the
            # 1536 default; the 64-program gate is the measured boundary's
            # conservative side. (All measured on an earlier device
            # set-up; crossover to be re-measured by a benchmark PR.)
            fn = partial(fn, min_flash_seq=512)
    elif impl == "flash_force":
        from ..ops.flash_attention import flash_attention as fn
    elif impl == "reference":
        from ..ops.ring_attention import reference_attention as fn
    else:
        Log.fatal(f"unknown attention impl {impl!r} "
                  "(expected 'reference', 'flash' or 'flash_force')")
    out = jax.vmap(partial(fn, causal=True))(split(q), split(k), split(v))
    return out.reshape(B, T, D)


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array) -> jax.Array:
    """Logits [B, T, V] for token ids [B, T] (causal LM)."""
    B, T = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0) + params["pos"][:T]

    def block(h, layer):
        x = _rmsnorm(h, layer["ln1_g"])
        # measured rejection (r5): concatenating w_q/w_k/w_v into one
        # [D, 3D] gemm saved only 0.18 ms of the 50.9 ms flagship step
        # (XLA already schedules the three thin gemms near-optimally);
        # not worth the concat + split in the hot path
        h = h + _attention(x @ layer["w_q"], x @ layer["w_k"],
                           x @ layer["w_v"], cfg.n_heads,
                           cfg.attention) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
        return h, None

    if cfg.scan_layers:
        # O(1) compile size for very deep stacks, at a measured ~30%
        # device-time cost (the scan's per-layer param slices and backward
        # grad-stack dynamic-update-slices are real HBM traffic)
        h, _ = jax.lax.scan(block, h, params["layers"])
    else:
        # unrolled (default): XLA schedules each layer's matmuls directly
        # with no carry copies — 112 ms -> 79 ms grad step at the
        # tools/lm_mfu.py flagship shape
        for i in range(cfg.n_layers):
            h, _ = block(h, jax.tree.map(lambda a: a[i], params["layers"]))
    h = _rmsnorm(h, params["ln_f_g"])
    return jnp.einsum("btd,vd->btv", h, params["embed"],
                      preferred_element_type=jnp.float32)


def loss_fn(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy over [B, T] token ids.

    Runs the forward at the FULL length and slices the logits, rather
    than slicing the tokens first: causal attention makes the two
    mathematically identical (position i sees only tokens <= i), but a
    T-1-length forward mis-tiles every flash call — the r5 trace showed
    the resulting pad/slice copies around all 12 layers' kernels cost
    ~1.4 ms/step (2.5%) at the flagship shape; the last position's
    logits row is orders of magnitude cheaper than that. Callers that
    feed ``max_seq + 1`` tokens (the LM app's chunking) keep the
    slice-first form — their sliced length IS the aligned one."""
    if tokens.shape[1] <= cfg.max_seq:
        logits = forward(cfg, params, tokens)[:, :-1]
    else:
        logits = forward(cfg, params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1))


# -- serving: KV-cache greedy decode -----------------------------------------
#
# The training ``forward`` recomputes attention over the whole prefix for
# every new token — O(T^2) per generated token. The serving path splits
# generation into PREFILL (one causal forward over the right-padded prompt
# batch that also records per-layer K/V projections) and DECODE (one token per
# step against the cached K/V — O(T) per token). Prompts are right-padded to
# the batcher's bucket length; per-example ``lengths`` drive the position
# embeddings, the logits gather, and the attention mask, so padding never
# leaks into a response. Cache layout: [n_layers, B, max_seq, d_model],
# pre-head-split (the head split is a free reshape).

_NEG_INF = -1e30


def _spread_heads(q, n_heads: int) -> Tuple[jax.Array, jax.Array]:
    """``q`` [B, D] spread block-diagonally to ``[B, h, D]`` (head
    ``h``'s ``dh`` columns, exact zeros elsewhere), and the ``[h, D]``
    mask of each head's own columns: with it a one-token attention is
    two MATRIX products over cache rows as they lie, ``[h, D] x [D, T]``
    and ``[h, T] x [T, D]``, and head ``h`` keeps its own columns of its
    row (:func:`_own_columns`). The added terms are exact zeros."""
    D = q.shape[1]
    own = (jnp.arange(n_heads)[:, None]
           == jnp.arange(D)[None, :] // (D // n_heads))
    return jnp.where(own[None], q[:, None, :], 0), own


def _own_columns(full, own) -> jax.Array:
    """Head ``h``'s own columns of row ``h`` of ``full`` [B, h, D]:
    the heads' outputs side by side, [B, D]."""
    return jnp.sum(jnp.where(own[None], full, 0.0), axis=1)


def _cached_attention(q, k_cache, v_cache, n_heads: int, pos) -> jax.Array:
    """One-token attention: ``q`` [B, D] against cache [B, T, D].

    ``pos`` [B] is each example's current position; cache entries at
    positions <= pos are live (prompt + previously generated tokens),
    everything past is masked. Same products and rounding points as
    :func:`ops.reference_attention` (1/sqrt(dh) scale, f32 accumulation,
    f32 softmax, probabilities rounded to the cache's dtype), but both
    products are written as MATRIX products over the cache as it lies,
    ``[B, T, D]``, with ``q`` spread block-diagonally
    (:func:`_spread_heads`). A one-row product per (example, head) is no
    matrix product to the TPU compiler: it multiplies and reduces on the
    vector units, over a float32 copy of the whole cache. The added
    terms are exact zeros, so only the order of the f32 accumulation is
    the compiler's.
    """
    T = k_cache.shape[1]
    q_heads, own = _spread_heads(q, n_heads)
    scores = jnp.einsum("bhD,btD->bht", q_heads, k_cache,
                        preferred_element_type=jnp.float32) / np.sqrt(
                            q.shape[1] // n_heads)
    mask = (jnp.arange(T)[None, :] <= pos[:, None])[:, None, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    full = jnp.einsum("bht,btD->bhD", probs.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32)
    return _own_columns(full, own).astype(q.dtype)


def prefill(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal forward over right-padded prompts, recording per-layer K/V.

    Returns ``(logits [B, P, V], k [L, B, P, D], v [L, B, P, D])``. Padding
    positions produce garbage hidden states — callers gather logits at
    ``lengths - 1`` and decode overwrites pad-slot cache entries before the
    mask ever reaches them, so the garbage is never observable.
    """
    B, P = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0) + params["pos"][:P]
    ks, vs = [], []
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        ks.append(k)
        vs.append(v)
        h = h + _attention(q, k, v, cfg.n_heads, cfg.attention) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    logits = jnp.einsum("btd,vd->btv", h, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits, jnp.stack(ks), jnp.stack(vs)


def _chunk_attention(q, k_cache, v_cache, n_heads: int, offset) -> jax.Array:
    """Chunk attention: ``q`` [C, D] against one slot's cache [T, D].

    Chunk position ``i`` (cache position ``offset + i``) attends cache
    entries at positions ``<= offset + i`` — the already-inserted prefix
    from earlier chunks plus this chunk's own K/V (written before the
    call), everything past is masked. Math matches
    :func:`_cached_attention` (1/sqrt(dh) scale, f32 softmax) so a
    chunked prefill's last-position logits argmax to the same first
    token the fused whole-prompt :func:`prefill` produces.
    """
    C, D = q.shape
    T = k_cache.shape[0]
    dh = D // n_heads
    qh = q.reshape(C, n_heads, dh)
    kh = k_cache.reshape(T, n_heads, dh)
    vh = v_cache.reshape(T, n_heads, dh)
    scores = jnp.einsum("chd,thd->hct", qh, kh,
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    mask = (jnp.arange(T)[None, :]
            <= (offset + jnp.arange(C))[:, None])[None, :, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hct,thd->chd", probs.astype(vh.dtype), vh)
    return out.reshape(C, D).astype(q.dtype)


# -- serving: paged KV cache --------------------------------------------------
#
# The decode engine's cache (vLLM/PagedAttention) is ONE block pool
# [L, n_blocks, block_size, D] plus a per-slot BLOCK TABLE
# [S, max_blocks_per_seq] of int32 block ids: logical cache position p of
# slot s lives at physical (block_tables[s, p // Bs], p % Bs), so a sequence
# holds only the blocks it needs, not a strip sized for the worst case
# T = max_prompt + max_new. Block tables are TRACED DATA (fixed [S, M]
# shape), so every program compiles once per engine config: reads are
# gathers through the table, writes are (block, offset) scatters, and which
# blocks a slot owns never touches a shape.
#
# Conventions shared by the paged entry points below (and by
# serving/block_pool.py, which owns the host-side allocator):
#
# * block id 0 is the SCRATCH block: the block-table pad sentinel, the
#   parking target for dead-lane decode writes, and where pad-position
#   prefill garbage lands. Nothing a live attention mask can reach ever
#   maps there — a slot's reservation covers prompt + max_new positions, so
#   every position <= pos resolves to a real allocated block.
# * per-slot views are built by ONE helper, :func:`_paged_view`, and SLICED
#   to the engine's logical cache length ``t_logical`` (= max_prompt +
#   max_new) before attention, so the attention operand is ``[.., T, D]``
#   whatever the block size: the same products and rounding points as
#   :func:`greedy_decode`'s cache, hence the same tokens. The gather's tail
#   positions past a slot's allocation hold scratch garbage, which the
#   position mask never reaches.


def _paged_view(pool: jax.Array, layer: int, tables: jax.Array) -> jax.Array:
    """Layer ``layer``'s blocks named by ``tables`` ([S, M] or [M] block
    ids), gathered straight from the ``[L, N, Bs, D]`` pool into a
    contiguous ``[..., M * Bs, D]`` view in the pool's dtype.

    The layer index goes INTO the gather (the pool seen as ``[L * N, Bs,
    D]``, a bitcast, indexed by ``layer * N + tables``): slicing
    ``pool[layer]`` out of a pool that was just scattered into copies
    the layer's whole ``[N, Bs, D]`` to read ``M`` blocks of it. Table
    entries are pool ids (scratch 0 included), always in bounds, so the
    gather clips instead of testing every element against the bounds.
    """
    L, N, Bs, D = pool.shape
    blocks = jnp.take(pool.reshape(L * N, Bs, D), layer * N + tables,
                      axis=0, mode="clip")
    return blocks.reshape(tables.shape[:-1] + (tables.shape[-1] * Bs, D))


def decode_step_paged(cfg: TransformerConfig, params: Dict[str, Any],
                      k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, tok: jax.Array,
                      pos: jax.Array, active: jax.Array,
                      t_logical: Optional[int] = None,
                      paged_attention: Optional[Callable] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused token step over S slots against the paged KV pool.

    ``k_pool``/``v_pool`` [L, N, Bs, D] (block 0 = scratch),
    ``block_tables`` [S, M] int32 (traced — one compiled trace per
    engine config regardless of block assignment), ``tok``/``pos`` [S]
    int32, ``active`` [S] bool. Each slot is an independent sequence
    (the math of :func:`greedy_decode`'s scan body, with the batch dim
    as the slot dim): a live slot writes its token's K/V at
    ``(block_tables[s, pos // Bs], pos % Bs)``, attends its gathered
    view sliced to ``t_logical`` through ``pos``, and emits its greedy
    next token. Dead slots still flow through the fused program (one
    compiled trace whichever slots live) but emit pad, keep a frozen
    ``pos`` and park their writes in the scratch block: never at the
    frozen ``pos``, which could sit inside a prompt region a chunked
    admission is prefilling between iterations, and scratch is never
    reachable by a live mask.

    ``paged_attention`` (:func:`ops.paged_attention.paged_mq_attention`,
    chosen by :func:`make_serving_programs` through
    ``ops.paged_attention.step_attention``) replaces "gather the view,
    two products over it" by a kernel that reads each slot's LIVE blocks
    out of the pools where they lie: the same block-diagonal query, the
    same rounding points, no ``[S, T, D]`` view.

    Returns ``(k_pool, v_pool, next_tok [S], pos [S])``.
    """
    L, N, Bs, D = k_pool.shape
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    blk = jnp.take_along_axis(block_tables, (pos // Bs)[:, None],
                              axis=1)[:, 0]
    write_blk = jnp.where(active, blk, 0)      # dead lanes -> scratch
    write_off = jnp.where(active, pos % Bs, 0)
    lengths = jnp.where(active, pos + 1, 0)
    h = (jnp.take(params["embed"], tok, axis=0)
         + jnp.take(params["pos"], pos, axis=0))
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        k_pool = k_pool.at[i, write_blk, write_off].set(k)
        v_pool = v_pool.at[i, write_blk, write_off].set(v)
        if paged_attention is not None:
            q_heads, own = _spread_heads(q, cfg.n_heads)
            full = paged_attention(
                q_heads, k_pool.reshape(L * N, Bs, D),
                v_pool.reshape(L * N, Bs, D), i * N + block_tables,
                lengths, scale=1.0 / np.sqrt(D // cfg.n_heads), wv=D)
            att = _own_columns(full, own).astype(q.dtype)
        else:
            # each slot's blocks as a contiguous [S, T, D] view: the
            # operand shape, products and rounding points of
            # greedy_decode's cache
            kc = _paged_view(k_pool, i, block_tables)
            vc = _paged_view(v_pool, i, block_tables)
            att = _cached_attention(q, kc[:, :T], vc[:, :T], cfg.n_heads,
                                    pos)
        h = h + att @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    out = jnp.einsum("sd,vd->sv", h, params["embed"],
                     preferred_element_type=jnp.float32)
    nxt = jnp.argmax(out, axis=-1).astype(tok.dtype)
    nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
    pos = jnp.where(active, pos + 1, pos)
    return k_pool, v_pool, nxt, pos


def prefill_chunk_paged(cfg: TransformerConfig, params: Dict[str, Any],
                        k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, slot: jax.Array,
                        tokens: jax.Array, offset: jax.Array,
                        length: jax.Array, t_logical: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Incremental prefill of one fixed-size chunk into the paged pool.

    ``tokens`` [C] right-padded chunk ids, ``slot`` the target slot,
    ``offset`` the cache position of ``tokens[0]``, ``length`` the real
    token count in this chunk (``1 <= length <= C``). All of slot/
    offset/length are traced scalars: ONE compiled trace per chunk size
    serves every (slot, offset, partial-fill) combination, next to the
    single fused :func:`decode_step_paged`.

    Each chunk position's K/V scatters to ``(block_tables[slot, p //
    Bs], p % Bs)`` BEFORE attention, so causal attention for position
    ``offset + i`` covers the already-inserted prefix ``[0, offset)``
    from earlier chunks plus the chunk's own positions ``<= i`` via
    :func:`_chunk_attention`'s mask over the slot's gathered view. Pad
    positions (``i >= length``) route to the scratch block explicitly:
    a final chunk's pad tail can extend past ``T`` (``ceil(P/C)*C``
    need not fit ``max_prompt + max_new``), and the table row gather
    clamps, so unmasked it would land on real prompt blocks, silent
    K/V corruption. Pad position-embedding reads clamp (``jnp.take``'s
    OOB mode) and their rows are never read.

    Returns ``(k_pool, v_pool, last_logits [V])``: the logits of
    position ``offset + length - 1``. Callers use them only on the
    FINAL chunk of a prompt, where they are the prompt's last real
    position: the first generated token falls out of the last chunk,
    exactly as it falls out of a whole-prompt :func:`prefill`.
    """
    C = tokens.shape[0]
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    bt_row = jax.lax.dynamic_index_in_dim(block_tables, slot, 0,
                                          keepdims=False)        # [M]
    pos_ix = offset + jnp.arange(C)
    valid = jnp.arange(C) < length
    blk = jnp.where(
        valid, jnp.take(bt_row, jnp.clip(pos_ix // Bs, 0, M - 1)), 0)
    off = jnp.where(valid, pos_ix % Bs, 0)
    h = (jnp.take(params["embed"], tokens, axis=0)
         + jnp.take(params["pos"], pos_ix, axis=0))
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        k_pool = k_pool.at[i, blk, off].set(k)
        v_pool = v_pool.at[i, blk, off].set(v)
        kc = _paged_view(k_pool, i, bt_row)
        vc = _paged_view(v_pool, i, bt_row)
        h = h + _chunk_attention(
            q, kc[:T], vc[:T], cfg.n_heads, offset) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    last = jnp.take(h, length - 1, axis=0)
    logits = jnp.einsum("d,vd->v", last, params["embed"],
                        preferred_element_type=jnp.float32)
    return k_pool, v_pool, logits


def prefill_chunk_paged_sp(cfg: TransformerConfig, params: Dict[str, Any],
                           k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, slot: jax.Array,
                           tokens: jax.Array, offset: jax.Array,
                           length: jax.Array, mesh, backend: str,
                           t_logical: Optional[int] = None,
                           tp_axis: str = "tp"
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel :func:`prefill_chunk_paged` over the decode mesh.

    Identical contract and — row for row — identical math: the only
    change is that the chunk's attention runs through
    :func:`ops.ring_prefill_attention` (``backend="ring"``) or
    :func:`ops.ulysses_prefill_attention` (``backend="ulysses"``), which
    shard the ``C`` chunk rows over the decode mesh's ``tp_axis`` and
    reassemble with collectives. Per-row chunk attention is independent
    of how rows are grouped across devices and the serving entry points
    reproduce ``_chunk_attention`` expression-for-expression, so outputs
    are bit-identical to the single-lane path; what changes is that a
    ``C = budget * tp`` chunk costs each device one budget's worth of
    rows per iteration, so a long prompt prefills in ``tp``x fewer
    iterations. Everything around the attention (embedding, K/V
    projections, paged scatter/gather, MLP) is left to GSPMD exactly as
    in the single-lane program. Requires ``C % tp == 0`` always and
    ``t_logical % tp == 0`` for the ring backend (the ulysses backend
    instead needs ``n_heads % tp == 0`` — the pool's native head shard).
    """
    if backend not in ("ring", "ulysses"):
        raise ValueError(f"unknown seqpar backend {backend!r}")
    C = tokens.shape[0]
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    bt_row = jax.lax.dynamic_index_in_dim(block_tables, slot, 0,
                                          keepdims=False)        # [M]
    pos_ix = offset + jnp.arange(C)
    valid = jnp.arange(C) < length
    blk = jnp.where(
        valid, jnp.take(bt_row, jnp.clip(pos_ix // Bs, 0, M - 1)), 0)
    off = jnp.where(valid, pos_ix % Bs, 0)
    h = (jnp.take(params["embed"], tokens, axis=0)
         + jnp.take(params["pos"], pos_ix, axis=0))
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        k_pool = k_pool.at[i, blk, off].set(k)
        v_pool = v_pool.at[i, blk, off].set(v)
        kc = _paged_view(k_pool, i, bt_row)
        vc = _paged_view(v_pool, i, bt_row)
        if backend == "ring":
            attn = ring_prefill_attention(q, kc[:T], vc[:T], cfg.n_heads,
                                          offset, mesh, axis=tp_axis)
        else:
            attn = ulysses_prefill_attention(q, kc[:T], vc[:T],
                                             cfg.n_heads, offset, mesh,
                                             axis=tp_axis)
        h = h + attn @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    last = jnp.take(h, length - 1, axis=0)
    logits = jnp.einsum("d,vd->v", last, params["embed"],
                        preferred_element_type=jnp.float32)
    return k_pool, v_pool, logits


# -- serving: speculative decoding (fixed-K verify step) ----------------------
#
# Speculative decoding amortizes per-step fixed costs (dispatch, host
# scheduling, all-reduces at decode_tp > 1) over up to K + 1 tokens per
# engine iteration: a host-side drafter proposes K cheap continuation
# guesses (n-gram prompt lookup — no draft model), and ONE fused forward
# scores all K + 1 positions against the paged pool. Greedy verification
# then accepts the longest drafted prefix that matches the model's own
# argmax chain plus one correction token, so outputs are token-identical
# to plain one-token decode by construction. The hard invariant survives:
# K is FIXED per engine config (the [S, K + 1] window is the only new
# static shape), while the drafted tokens, per-slot valid counts, block
# tables and positions are all traced data — exactly one compiled verify
# trace per engine config, next to the one fused step.


def _verify_attention(q, k_cache, v_cache, n_heads: int, pos) -> jax.Array:
    """Windowed multi-position attention: ``q`` [S, K1, D] against each
    slot's gathered cache [S, T, D].

    Window position ``j`` of slot ``s`` sits at cache position
    ``pos[s] + j`` and attends entries at positions ``<= pos[s] + j`` —
    the committed prefix plus the window's own already-written K/V
    (causal WITHIN the drafted window, exactly
    :func:`_chunk_attention`'s mask with the chunk offset per slot).
    Math matches :func:`_cached_attention` (1/sqrt(dh) scale, f32
    softmax), so window position 0's argmax is the token the plain
    fused step would emit.
    """
    S, K1, D = q.shape
    T = k_cache.shape[1]
    dh = D // n_heads
    qh = q.reshape(S, K1, n_heads, dh)
    kh = k_cache.reshape(S, T, n_heads, dh)
    vh = v_cache.reshape(S, T, n_heads, dh)
    scores = jnp.einsum("skhd,sthd->shkt", qh, kh,
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    mask = (jnp.arange(T)[None, None, :]
            <= (pos[:, None] + jnp.arange(K1))[:, :, None])[:, None, :, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("shkt,sthd->skhd", probs.astype(vh.dtype), vh)
    return out.reshape(S, K1, D).astype(q.dtype)


def verify_step_paged(cfg: TransformerConfig, params: Dict[str, Any],
                      k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, toks: jax.Array,
                      pos: jax.Array, active: jax.Array,
                      n_valid: jax.Array, t_logical: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused multi-position step: score K drafted tokens in one forward.

    ``toks`` [S, K1] is each slot's verification window — position 0 is
    the token the plain step would consume, positions ``1 .. K1 - 1``
    are drafted guesses; ``pos`` [S] is the cache position of
    ``toks[:, 0]``; ``n_valid`` [S] int32 in ``[1, K1]`` counts each
    slot's REAL window entries (a slot with no drafts this iteration
    runs ``n_valid = 1``). K1 = K + 1 is the ONLY static the feature
    adds: toks/pos/active/n_valid and the block tables are all traced,
    so one compiled trace serves every draft mix, acceptance outcome
    and block assignment — the accepted length is handled host-side as
    data, never as a shape.

    Every valid window position writes its K/V at
    ``(block_tables[s, (pos + j) // Bs], (pos + j) % Bs)`` BEFORE
    attention (so the causal window sees itself), then attends the
    slot's gathered view sliced to ``t_logical`` via
    :func:`_verify_attention`. Dead lanes and pad positions
    (``j >= n_valid``) park their writes in the scratch block — the
    engine clamps drafts to ``remaining - 1`` tokens, so valid writes
    never escape the slot's admission-time reservation and rejected
    positions need NO device-side rollback: the next window starts at
    the first unverified position and rewrites every speculated
    position before any mask can reach it (the same
    overwrite-before-the-mask contract pad garbage already rides).

    Returns ``(k_pool, v_pool, out_tok [S, K1])`` where
    ``out_tok[s, j]`` is the greedy token following inputs
    ``toks[s, : j + 1]``: the host accepts drafts while
    ``toks[s, j] == out_tok[s, j - 1]`` and emits
    ``out_tok[s, : accepted + 1]`` — position ``accepted``'s entry is
    the correction token, so every iteration emits at least the one
    token the plain step would have.
    """
    K1 = toks.shape[1]
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    pos_ix = pos[:, None] + jnp.arange(K1)[None, :]            # [S, K1]
    valid = (jnp.arange(K1)[None, :] < n_valid[:, None]) & active[:, None]
    blk = jnp.where(
        valid,
        jnp.take_along_axis(block_tables,
                            jnp.clip(pos_ix // Bs, 0, M - 1), axis=1), 0)
    off = jnp.where(valid, pos_ix % Bs, 0)
    h = (jnp.take(params["embed"], toks, axis=0)
         + jnp.take(params["pos"], pos_ix, axis=0))
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        k_pool = k_pool.at[i, blk, off].set(k)
        v_pool = v_pool.at[i, blk, off].set(v)
        kc = _paged_view(k_pool, i, block_tables)
        vc = _paged_view(v_pool, i, block_tables)
        h = h + _verify_attention(
            q, kc[:, :T], vc[:, :T], cfg.n_heads, pos) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    out = jnp.einsum("skd,vd->skv", h, params["embed"],
                     preferred_element_type=jnp.float32)
    nxt = jnp.argmax(out, axis=-1).astype(toks.dtype)
    return k_pool, v_pool, jnp.where(valid, nxt, jnp.zeros_like(nxt))


# -- serving: tensor-parallel sharded decode ----------------------------------
#
# PR 2 gated decode to a single-device params replica because feeding the
# train mesh's ``NamedSharding``s to the tiny per-token programs dragged
# every call through the spmd partitioner (~10x step wall). That was a
# workaround with a hard ceiling: params + KV pool had to fit ONE device.
# The fix is a DECODE-SPECIFIC mesh — Megatron-style tensor parallelism over
# attention heads and the MLP hidden dim, applied to autoregressive decode
# the way Pope et al. apply it: weights and KV cache partitioned ONCE, and
# every serving program jitted ONCE against matched ``in_shardings``/
# ``out_shardings`` (the pre-partitioned-pjit pattern — when a call's inputs
# already carry the shardings the program was compiled for, dispatch never
# goes back through the partitioner). The paged K/V pools
# ``[L, n_blocks + 1, Bs, D]`` shard over the head slice of ``D``; block
# tables, token ids, positions and the active mask stay REPLICATED
# traced-as-data, so the one-compiled-trace-per-engine-config invariant
# holds per mesh exactly as it does on one device.
#
# Head-sharding math: ``D = n_heads * dh`` and the decode kernels reshape
# ``[..., D] -> [..., n_heads, dh]``. Sharding ``D`` into ``tp`` contiguous
# slices of ``D/tp = (n_heads/tp) * dh`` therefore lands WHOLE heads on each
# device: the reshape is a local split (no resharding), attention is
# embarrassingly parallel over its head axis, and the only collectives are
# the two Megatron all-reduces per layer (after row-parallel ``w_o`` and
# ``w_ff2``). Requires ``tp | n_heads`` and ``tp | d_ff``.

DECODE_TP_AXIS = "tp"


def validate_decode_tp(cfg: TransformerConfig, tp: int,
                       name: str = "decode") -> None:
    """Fail fast on a tp width the head-sharding math cannot honour."""
    if tp < 1:
        Log.fatal(f"{name}: decode_tp must be >= 1, got {tp}")
    if cfg.n_heads % tp != 0:
        Log.fatal(f"{name}: decode_tp {tp} does not divide n_heads "
                  f"{cfg.n_heads} — head sharding needs whole heads per "
                  f"device")
    if cfg.d_ff % tp != 0:
        Log.fatal(f"{name}: decode_tp {tp} does not divide d_ff "
                  f"{cfg.d_ff} — the MLP hidden dim shards over tp")


def decode_param_shardings(mesh, tp_axis: str = DECODE_TP_AXIS
                           ) -> Dict[str, Any]:
    """Serving-param layout under the decode mesh.

    Column-parallel ``w_q``/``w_k``/``w_v``/``w_ff1`` (output dim — the
    head slice of ``D`` / the hidden dim — sharded), row-parallel
    ``w_o``/``w_ff2`` (input dim sharded, partial sums all-reduced by
    XLA). Embeddings REPLICATE, unlike the train layout's row shard: the
    decode logits einsum contracts over ``d`` for an ``[S, V]`` output
    that is already tiny, and a vocab shard would all-gather it every
    token; positions and norms replicate as always.
    """
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    return {
        "embed": ns(),
        "pos": ns(),
        "layers": {
            "ln1_g": ns(),
            "ln2_g": ns(),
            "w_q": ns(None, None, tp_axis),
            "w_k": ns(None, None, tp_axis),
            "w_v": ns(None, None, tp_axis),
            "w_o": ns(None, tp_axis, None),
            "w_ff1": ns(None, None, tp_axis),
            "w_ff2": ns(None, tp_axis, None),
        },
        "ln_f_g": ns(),
    }


def kv_pool_sharding(mesh, tp_axis: str = DECODE_TP_AXIS) -> NamedSharding:
    """Paged K/V pools ``[L, n_blocks + 1, Bs, D]`` sharded over the
    head slice of ``D`` — each device holds its heads' cache for every
    block, so table gathers/scatters stay device-local."""
    return NamedSharding(mesh, P(None, None, None, tp_axis))


def cow_block_copy(k_pool: jax.Array, v_pool: jax.Array, src: jax.Array,
                   dst: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Copy-on-write block duplication across both pools; ``src``/``dst``
    are traced block ids (one compiled trace serves every copy)."""
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]))


# -- serving: int8 per-block-scaled paged KV cache ----------------------------
#
# The ``_q`` variants below store the paged pools as int8 with ONE fp32
# scale per (layer, block) — ``k_scales``/``v_scales`` [L, N] arrays that
# ride every program as TRACED OPERANDS next to the block tables (never
# static), so the one-compiled-trace-per-engine-config invariant is
# untouched: which blocks hold what scale is data, exactly like which
# blocks a slot owns.
#
# Write semantics (quantize-on-write): gather the affected blocks,
# dequantize with the OLD scale, insert the new fp32 rows, then requantize
# the whole block against ``new_scale = max(old_scale, rowmax / 127)``.
# Two properties make this sound:
#
# * **identity when the scale is unchanged** — ``round(q * s / s) == q``
#   exactly for |q| <= 127 in fp32, so re-quantizing untouched rows (and
#   untouched blocks swept up by a whole-row scatter: scratch padding,
#   shared prefix blocks visible from several tables) rewrites their
#   exact bytes — repeated writes cause NO drift, and duplicate scatters
#   carry identical values (deterministic). A scale GROWTH re-rounds the
#   block's earlier rows once onto the coarser grid — the per-block-scale
#   trade, bounded by one rounding step.
# * **reset at block entry** — the first write into a block (block-local
#   offset 0) discards the previous occupant's scale instead of
#   max-merging it, so a freed-and-reallocated block cannot ratchet the
#   pool's scales up forever. The stale occupant's rows requantize as
#   clipped garbage under the new scale — finite, and never reachable by
#   a live attention mask before being overwritten (the standard pad
#   contract).
#
# Reads (dequantize-on-gather) multiply the gathered int8 view by its
# gathered scales before attention, so the operand shape (and masking)
# matches the fp32 kernels exactly; quality is measured as argmax-match
# rate against the fp32 oracle (docs/SERVING.md "Quantized KV & params").

_KV_QMAX = 127.0


def _kv_q_safe(scale: jax.Array) -> jax.Array:
    """Zero-divide guard: an all-zero (never-written / reset) block keeps
    scale 0 and dequantizes to exact zeros; dividing by 1 there quantizes
    zeros to zeros."""
    return jnp.where(scale > 0, scale, jnp.ones_like(scale))


def _kv_q_requant(rows: jax.Array, scale: jax.Array) -> jax.Array:
    """fp32 ``rows`` [..., Bs, D] against per-block ``scale`` [...] ->
    int8 (symmetric, clipped)."""
    q = jnp.round(rows / _kv_q_safe(scale)[..., None, None])
    return jnp.clip(q, -_KV_QMAX, _KV_QMAX).astype(jnp.int8)


def _kv_q_dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 blocks [..., Bs, D] * per-block ``scale`` [...] -> fp32."""
    return q.astype(jnp.float32) * scale[..., None, None]


def decode_step_paged_q(cfg: TransformerConfig, params: Dict[str, Any],
                        k_pool: jax.Array, v_pool: jax.Array,
                        k_scales: jax.Array, v_scales: jax.Array,
                        block_tables: jax.Array, tok: jax.Array,
                        pos: jax.Array, active: jax.Array,
                        t_logical: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array, jax.Array, jax.Array]:
    """Quantized :func:`decode_step_paged`: int8 pools [L, N, Bs, D] +
    fp32 ``k_scales``/``v_scales`` [L, N]. Each live slot writes exactly
    ONE block (exclusively owned — the engine CoWs shared blocks before
    any write), so the write is a per-slot gather/requant/scatter of that
    block; dead lanes park on scratch, where order-undefined duplicates
    are unobservable exactly as in the fp32 kernel.

    Returns ``(k_pool, v_pool, k_scales, v_scales, next_tok, pos)``.
    """
    S = tok.shape[0]
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    blk = jnp.take_along_axis(block_tables, (pos // Bs)[:, None],
                              axis=1)[:, 0]
    write_blk = jnp.where(active, blk, 0)      # dead lanes -> scratch
    write_off = jnp.where(active, pos % Bs, 0)
    lanes = jnp.arange(S)
    h = (jnp.take(params["embed"], tok, axis=0)
         + jnp.take(params["pos"], pos, axis=0))

    def write(pool, scales, rows):
        cur_s = jnp.take(scales, write_blk, axis=0)            # [S]
        cur = _kv_q_dequant(jnp.take(pool, write_blk, axis=0), cur_s)
        rows32 = rows.astype(jnp.float32)
        cur = cur.at[lanes, write_off].set(rows32)
        # entering a fresh block (offset 0) drops the prior occupant's
        # scale; otherwise scales only grow within an occupancy
        base = jnp.where(write_off == 0, 0.0, cur_s)
        new_s = jnp.maximum(base,
                            jnp.max(jnp.abs(rows32), axis=-1) / _KV_QMAX)
        return (pool.at[write_blk].set(_kv_q_requant(cur, new_s)),
                scales.at[write_blk].set(new_s))

    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        kp, ks = write(k_pool[i], k_scales[i], k)
        vp, vs = write(v_pool[i], v_scales[i], v)
        k_pool, k_scales = k_pool.at[i].set(kp), k_scales.at[i].set(ks)
        v_pool, v_scales = v_pool.at[i].set(vp), v_scales.at[i].set(vs)
        kv_shape = (S, M * Bs, -1)
        kc = _kv_q_dequant(
            _paged_view(k_pool, i, block_tables).reshape(S, M, Bs, -1),
            jnp.take(k_scales[i], block_tables, axis=0)
        ).astype(h.dtype).reshape(kv_shape)
        vc = _kv_q_dequant(
            _paged_view(v_pool, i, block_tables).reshape(S, M, Bs, -1),
            jnp.take(v_scales[i], block_tables, axis=0)
        ).astype(h.dtype).reshape(kv_shape)
        h = h + _cached_attention(
            q, kc[:, :T], vc[:, :T], cfg.n_heads, pos) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    out = jnp.einsum("sd,vd->sv", h, params["embed"],
                     preferred_element_type=jnp.float32)
    nxt = jnp.argmax(out, axis=-1).astype(tok.dtype)
    nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
    pos = jnp.where(active, pos + 1, pos)
    return k_pool, v_pool, k_scales, v_scales, nxt, pos


def prefill_chunk_paged_q(cfg: TransformerConfig, params: Dict[str, Any],
                          k_pool: jax.Array, v_pool: jax.Array,
                          k_scales: jax.Array, v_scales: jax.Array,
                          block_tables: jax.Array, slot: jax.Array,
                          tokens: jax.Array, offset: jax.Array,
                          length: jax.Array, t_logical: Optional[int] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array, jax.Array]:
    """Quantized :func:`prefill_chunk_paged`: the chunk's writes span
    several blocks of ONE slot, so the kernel works on the slot's whole
    table row — gather all M blocks, dequantize, scatter the chunk's
    rows into the flat [M*Bs, D] view (invalid lanes get out-of-range
    indices and DROP — the paged pad contract without the scratch
    detour), fold per-block scale contributions in with a scatter-max,
    requantize the row, scatter it back. Untouched blocks requantize to
    their exact old bytes (identity), so the row-wide scatter is safe.

    Returns ``(k_pool, v_pool, k_scales, v_scales, last_logits)``.
    """
    C = tokens.shape[0]
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    bt_row = jax.lax.dynamic_index_in_dim(block_tables, slot, 0,
                                          keepdims=False)        # [M]
    pos_ix = offset + jnp.arange(C)
    valid = jnp.arange(C) < length
    flat_ix = jnp.where(valid, pos_ix, M * Bs)       # OOB lanes drop
    blk_local = jnp.where(valid, jnp.clip(pos_ix // Bs, 0, M - 1), M)
    fresh = (valid & (pos_ix % Bs == 0)).astype(jnp.float32)
    h = (jnp.take(params["embed"], tokens, axis=0)
         + jnp.take(params["pos"], pos_ix, axis=0))

    def write(pool, scales, rows):
        row_s = jnp.take(scales, bt_row, axis=0)                 # [M]
        flat = _kv_q_dequant(jnp.take(pool, bt_row, axis=0),
                             row_s).reshape(M * Bs, -1)
        rows32 = rows.astype(jnp.float32)
        flat = flat.at[flat_ix].set(rows32, mode="drop")
        reset = jnp.zeros((M,), jnp.float32).at[blk_local].max(
            fresh, mode="drop") > 0
        contrib = jnp.zeros((M,), jnp.float32).at[blk_local].max(
            jnp.where(valid, jnp.max(jnp.abs(rows32), axis=-1), 0.0),
            mode="drop")
        new_s = jnp.maximum(jnp.where(reset, 0.0, row_s),
                            contrib / _KV_QMAX)
        new_q = _kv_q_requant(flat.reshape(M, Bs, -1), new_s)
        return (pool.at[bt_row].set(new_q), scales.at[bt_row].set(new_s),
                _kv_q_dequant(new_q, new_s).reshape(M * Bs, -1))

    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        kp, ks, kc = write(k_pool[i], k_scales[i], k)
        vp, vs, vc = write(v_pool[i], v_scales[i], v)
        k_pool, k_scales = k_pool.at[i].set(kp), k_scales.at[i].set(ks)
        v_pool, v_scales = v_pool.at[i].set(vp), v_scales.at[i].set(vs)
        h = h + _chunk_attention(
            q, kc[:T].astype(h.dtype), vc[:T].astype(h.dtype),
            cfg.n_heads, offset) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    last = jnp.take(h, length - 1, axis=0)
    logits = jnp.einsum("d,vd->v", last, params["embed"],
                        preferred_element_type=jnp.float32)
    return k_pool, v_pool, k_scales, v_scales, logits


def verify_step_paged_q(cfg: TransformerConfig, params: Dict[str, Any],
                        k_pool: jax.Array, v_pool: jax.Array,
                        k_scales: jax.Array, v_scales: jax.Array,
                        block_tables: jax.Array, toks: jax.Array,
                        pos: jax.Array, active: jax.Array,
                        n_valid: jax.Array, t_logical: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array, jax.Array]:
    """Quantized :func:`verify_step_paged`: the whole-row form of
    :func:`prefill_chunk_paged_q` per slot — a window can write several
    positions of one block, so per-position block scatters would race;
    instead every slot's full table row round-trips through fp32. Blocks
    a slot does not validly write (shared prefix blocks visible from
    several rows, scratch padding) requantize to their exact old bytes,
    so the cross-slot duplicate scatters all carry identical values.

    Returns ``(k_pool, v_pool, k_scales, v_scales, out_tok [S, K1])``.
    """
    S, K1 = toks.shape
    Bs = k_pool.shape[2]
    M = block_tables.shape[1]
    T = M * Bs if t_logical is None else int(t_logical)
    pos_ix = pos[:, None] + jnp.arange(K1)[None, :]            # [S, K1]
    valid = (jnp.arange(K1)[None, :] < n_valid[:, None]) & active[:, None]
    flat_ix = jnp.where(valid, pos_ix, M * Bs)       # OOB lanes drop
    blk_local = jnp.where(valid, jnp.clip(pos_ix // Bs, 0, M - 1), M)
    fresh = (valid & (pos_ix % Bs == 0)).astype(jnp.float32)
    lanes = jnp.arange(S)[:, None]
    h = (jnp.take(params["embed"], toks, axis=0)
         + jnp.take(params["pos"], pos_ix, axis=0))

    def write(pool, scales, rows):
        rows_s = jnp.take(scales, block_tables, axis=0)        # [S, M]
        flat = _kv_q_dequant(jnp.take(pool, block_tables, axis=0),
                             rows_s).reshape(S, M * Bs, -1)
        rows32 = rows.astype(jnp.float32)
        flat = flat.at[lanes, flat_ix].set(rows32, mode="drop")
        reset = jnp.zeros((S, M), jnp.float32).at[lanes, blk_local].max(
            fresh, mode="drop") > 0
        contrib = jnp.zeros((S, M), jnp.float32).at[lanes, blk_local].max(
            jnp.where(valid, jnp.max(jnp.abs(rows32), axis=-1), 0.0),
            mode="drop")
        new_s = jnp.maximum(jnp.where(reset, 0.0, rows_s),
                            contrib / _KV_QMAX)
        new_q = _kv_q_requant(flat.reshape(S, M, Bs, -1), new_s)
        return (pool.at[block_tables].set(new_q),
                scales.at[block_tables].set(new_s),
                _kv_q_dequant(new_q, new_s).reshape(S, M * Bs, -1))

    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x = _rmsnorm(h, layer["ln1_g"])
        q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
        kp, ks, kc = write(k_pool[i], k_scales[i], k)
        vp, vs, vc = write(v_pool[i], v_scales[i], v)
        k_pool, k_scales = k_pool.at[i].set(kp), k_scales.at[i].set(ks)
        v_pool, v_scales = v_pool.at[i].set(vp), v_scales.at[i].set(vs)
        h = h + _verify_attention(
            q, kc[:, :T].astype(h.dtype), vc[:, :T].astype(h.dtype),
            cfg.n_heads, pos) @ layer["w_o"]
        x = _rmsnorm(h, layer["ln2_g"])
        h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
    h = _rmsnorm(h, params["ln_f_g"])
    out = jnp.einsum("skd,vd->skv", h, params["embed"],
                     preferred_element_type=jnp.float32)
    nxt = jnp.argmax(out, axis=-1).astype(toks.dtype)
    return (k_pool, v_pool, k_scales, v_scales,
            jnp.where(valid, nxt, jnp.zeros_like(nxt)))


def cow_block_copy_q(k_pool: jax.Array, v_pool: jax.Array,
                     k_scales: jax.Array, v_scales: jax.Array,
                     src: jax.Array, dst: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                jax.Array]:
    """Quantized :func:`cow_block_copy`: the duplicate carries its
    source's int8 bytes AND its scale column — content-identical by
    construction."""
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]),
            k_scales.at[:, dst].set(k_scales[:, src]),
            v_scales.at[:, dst].set(v_scales[:, src]))


# -- serving: quantized decode param snapshots --------------------------------


def _is_quant_param_leaf(x: Any) -> bool:
    return isinstance(x, dict) and set(x.keys()) == {"q", "s"}


def dequantize_decode_params(qparams: Any, dtype=jnp.float32) -> Any:
    """Traced inverse of :func:`serving.snapshot.quantize_decode_params`:
    each ``{"q": int8, "s": fp32}`` leaf multiplies out to ``dtype``.
    Expressed as ordinary jnp ops at the TOP of a jitted decode program,
    so XLA folds the dequant into the compiled module — per-device param
    residency is the int8 pytree, and the program's one-trace accounting
    never notices (``decode_step_retraces`` stays 0)."""
    return jax.tree.map(
        lambda leaf: (leaf["q"].astype(jnp.float32)
                      * leaf["s"]).astype(dtype),
        qparams, is_leaf=_is_quant_param_leaf)


def decode_param_quant_shardings(mesh, tp_axis: str = DECODE_TP_AXIS
                                 ) -> Dict[str, Any]:
    """Decode-mesh shardings for the QUANTIZED param pytree: each leaf's
    ``q`` carries the weight's :func:`decode_param_shardings` spec (same
    shape as the weight, so the spec applies unchanged) and the tiny
    ``s`` scales REPLICATE — a keepdims per-column scale has a size-1
    dim exactly where the row-parallel specs shard, so replication is
    the only layout that fits every leaf (and costs ~nothing)."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda s: {"q": s, "s": rep},
                        decode_param_shardings(mesh, tp_axis))


def make_sharded_decode_programs(cfg: TransformerConfig, mesh,
                                 t_logical: int, donate: bool = False,
                                 tp_axis: str = DECODE_TP_AXIS,
                                 kv_quant: str = "none",
                                 param_quant: str = "none",
                                 prefill_sp: str = "none"
                                 ) -> Dict[str, Any]:
    """Pre-partitioned decode-mesh variants of the paged serving programs.

    Returns ``{"step", "chunk", "cow", "verify", "param_shardings",
    "pool_sharding"}`` — each program jitted exactly
    once with matched
    ``in_shardings``/``out_shardings``: params carry
    :func:`decode_param_shardings`, both pools carry
    :func:`kv_pool_sharding` (outputs included, so iteration N's pools
    re-enter iteration N+1 already partitioned), and everything traced
    as data (block tables, tokens, positions, masks, scalars)
    replicates. A caller that pins its params via
    ``serving.snapshot.shard_for_decode`` and round-trips the pools
    through these programs never re-enters the spmd partitioner after
    the first compile — the construction-time contract ``DecodeEngine``
    builds these under (``__init__``/``warmup`` only; RT106).

    ``kv_quant="int8"`` returns the quantized program set instead: each
    program additionally takes/returns the fp32 ``k_scales``/``v_scales``
    [L, N] operands (REPLICATED — they are KBs next to the pools' MBs,
    and the per-block scale multiplies the full ``D`` of its block, so a
    head-shard would buy nothing), with the int8 pools still sharded
    over the head slice of ``D``. ``param_quant="int8"`` makes every
    program accept the quantized param pytree
    (:func:`serving.snapshot.quantize_decode_params` leaves), sharded per
    :func:`decode_param_quant_shardings`, with
    :func:`dequantize_decode_params` folded in at compile time. Both
    default off; the default programs are exactly the pre-quantization
    ones.

    ``prefill_sp="ring"|"ulysses"`` adds a ``"chunk_sp"`` program — the
    sequence-parallel :func:`prefill_chunk_paged_sp` jitted with the
    SAME shardings/donation as ``"chunk"``; the chunk size rides the
    token-array shape (the engine passes ``budget * tp`` tokens, one
    budget's worth of rows per device). It rides next to, not
    instead of, the single-lane ``"chunk"``: the engine routes prompts
    by ``prefill_sp_threshold``. Incompatible with ``kv_quant="int8"``.
    """
    if prefill_sp != "none" and kv_quant == "int8":
        raise ValueError("prefill_sp is incompatible with kv_quant=int8")
    if param_quant == "int8":
        ps = decode_param_quant_shardings(mesh, tp_axis)
        pf = lambda p: dequantize_decode_params(p, cfg.dtype)
    else:
        ps = decode_param_shardings(mesh, tp_axis)
        pf = lambda p: p
    pool = kv_pool_sharding(mesh, tp_axis)
    rep = NamedSharding(mesh, P())
    T = int(t_logical)
    if kv_quant == "int8":
        # pools at positions 1-2, scales at 3-4: donate all four (the
        # scales round-trip every program exactly like the pools)
        kv_donate = (1, 2, 3, 4) if donate else ()
        step = jax.jit(
            lambda params, kc, vc, ksc, vsc, bt, tok, pos, active:
            decode_step_paged_q(cfg, pf(params), kc, vc, ksc, vsc, bt,
                                tok, pos, active, t_logical=T),
            in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep, rep),
            out_shardings=(pool, pool, rep, rep, rep, rep),
            donate_argnums=kv_donate)
        chunk = jax.jit(
            lambda params, kc, vc, ksc, vsc, bt, slot, toks, off, n:
            prefill_chunk_paged_q(cfg, pf(params), kc, vc, ksc, vsc, bt,
                                  slot, toks, off, n, t_logical=T),
            in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep, rep,
                          rep),
            out_shardings=(pool, pool, rep, rep, rep),
            donate_argnums=kv_donate)
        cow = jax.jit(
            lambda kc, vc, ksc, vsc, src, dst: cow_block_copy_q(
                kc, vc, ksc, vsc, src, dst),
            in_shardings=(pool, pool, rep, rep, rep, rep),
            out_shardings=(pool, pool, rep, rep),
            donate_argnums=(0, 1, 2, 3) if donate else ())
        verify = jax.jit(
            lambda params, kc, vc, ksc, vsc, bt, toks, pos, active, nv:
            verify_step_paged_q(cfg, pf(params), kc, vc, ksc, vsc, bt,
                                toks, pos, active, nv, t_logical=T),
            in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep, rep,
                          rep),
            out_shardings=(pool, pool, rep, rep, rep),
            donate_argnums=kv_donate)
        return {"step": step, "chunk": chunk, "cow": cow,
                "verify": verify, "param_shardings": ps,
                "pool_sharding": pool}
    kv_donate = (1, 2) if donate else ()
    step = jax.jit(
        lambda params, kc, vc, bt, tok, pos, active: decode_step_paged(
            cfg, pf(params), kc, vc, bt, tok, pos, active, t_logical=T),
        in_shardings=(ps, pool, pool, rep, rep, rep, rep),
        out_shardings=(pool, pool, rep, rep),
        donate_argnums=kv_donate)
    chunk = jax.jit(
        lambda params, kc, vc, bt, slot, toks, off, n: prefill_chunk_paged(
            cfg, pf(params), kc, vc, bt, slot, toks, off, n, t_logical=T),
        in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep),
        out_shardings=(pool, pool, rep),
        donate_argnums=kv_donate)
    # every program wraps in a FRESH lambda (cow included): jit caches
    # key on the function object, so jitting a shared module-level
    # function directly would pool every engine's compiled traces on
    # one handle and break per-engine one-trace accounting
    cow = jax.jit(
        lambda kc, vc, src, dst: cow_block_copy(kc, vc, src, dst),
        in_shardings=(pool, pool, rep, rep),
        out_shardings=(pool, pool),
        donate_argnums=(0, 1) if donate else ())
    # the speculative verify step pins and partitions exactly like the
    # fused step: params sharded, pools round-tripped pool-sharded, the
    # [S, K + 1] window / positions / valid counts replicated traced-as-
    # data. K rides the window SHAPE, so the engine (which always passes
    # its fixed spec_k + 1 columns) gets exactly one compiled trace; a
    # spec_k=0 engine never dispatches it and its cache stays empty.
    verify = jax.jit(
        lambda params, kc, vc, bt, toks, pos, active, nv:
        verify_step_paged(cfg, pf(params), kc, vc, bt, toks, pos, active,
                          nv, t_logical=T),
        in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep),
        out_shardings=(pool, pool, rep),
        donate_argnums=kv_donate)
    progs = {"step": step, "chunk": chunk, "cow": cow, "verify": verify,
             "param_shardings": ps, "pool_sharding": pool}
    if prefill_sp != "none":
        progs["chunk_sp"] = jax.jit(
            lambda params, kc, vc, bt, slot, toks, off, n:
            prefill_chunk_paged_sp(cfg, pf(params), kc, vc, bt, slot,
                                   toks, off, n, mesh, prefill_sp,
                                   t_logical=T, tp_axis=tp_axis),
            in_shardings=(ps, pool, pool, rep, rep, rep, rep, rep),
            out_shardings=(pool, pool, rep),
            donate_argnums=kv_donate)
    return progs


def make_serving_programs(cfg: TransformerConfig, spec) -> Any:
    """``TransformerLM``'s side of the engine's seam
    (``serving/programs.py``): two ``[L, N + 1, Bs, d_model]`` K/V
    pools (``kv_quant="int8"``: int8 pools and two ``[L, N + 1]``
    float32 scale arrays) and the
    programs of this module over them, each jitted ONCE here, at engine
    construction (RT106). ``spec`` is the engine's resolved
    :class:`serving.programs.EngineSpec`."""
    from ..serving.block_pool import kv_bytes_per_block
    from ..serving.programs import ServingPrograms
    from ..serving.snapshot import (quantize_decode_params,
                                    replicate_for_decode, shard_for_decode)

    who = f"DecodeEngine {spec.name!r}"
    T = spec.cache_len
    if T > cfg.max_seq:
        Log.fatal(f"{who}: max_prompt {spec.max_prompt} + "
                  f"max_new {spec.max_new} exceeds max_seq {cfg.max_seq}")
    L, D = cfg.n_layers, cfg.d_model
    quant = spec.kv_quant == "int8"
    pool_shape = (L, spec.pool_blocks + 1, spec.block_size, D)
    pool_dtype = jnp.dtype(jnp.int8 if quant else cfg.dtype)
    pools = [(pool_shape, pool_dtype)] * 2
    if quant:
        # per-(layer, block) fp32 scales, one array per pool; zeros from
        # birth: scale 0 marks a never-written block
        pools += [((L, spec.pool_blocks + 1), jnp.dtype(jnp.float32))] * 2
    n = len(pools)
    out = ServingPrograms(
        pools=tuple(pools), step=None,
        bytes_per_block=kv_bytes_per_block(
            L, D, spec.block_size, np.dtype(cfg.dtype), quant=spec.kv_quant),
        scale_pools=(2, 3) if quant else ())
    prequant = (quantize_decode_params if spec.param_quant == "int8"
                else (lambda value: value))

    if spec.tp > 1:
        # decode-mesh programs, pre-partitioned: every program is jitted
        # ONCE with matched in/out_shardings, so the partitioner runs at
        # compile and never again; params arrive resharded by the pin
        # (shard_for_decode) and the pools round-trip with their
        # sharding intact. Copy-on-write rides the same mesh.
        validate_decode_tp(cfg, spec.tp, name=who)
        progs = make_sharded_decode_programs(
            cfg, spec.mesh, T, donate=spec.donate, kv_quant=spec.kv_quant,
            param_quant=spec.param_quant, prefill_sp=spec.prefill_sp)
        out.param_shardings = progs["param_shardings"]
        # on a sharded engine the scales REPLICATE: [L, N] has no head
        # slice to shard, and every shard needs every block's scale
        out.pool_targets = (progs["pool_sharding"],) * 2 + (
            (NamedSharding(spec.mesh, P()),) * 2 if quant else ())
        out.chunk, out.step = progs["chunk"], progs["step"]
        out.chunk_sp = progs.get("chunk_sp")
        out.cow = progs["cow"] if spec.prefix else None
        out.verify = progs["verify"] if spec.spec_k else None
        shardings, mesh = out.param_shardings, spec.mesh
        out.pin = lambda value: shard_for_decode(prequant(value), mesh,
                                                 shardings)
    else:
        out.pin = lambda value: replicate_for_decode(prequant(value))
        # cache donation is real only where XLA implements input
        # aliasing; the quant programs thread (kc, vc, ksc, vsc) after
        # params, so the donate tuple covers all of the pools
        donate = tuple(range(1, n + 1)) if spec.donate else ()
        cow_donate = tuple(range(n)) if spec.donate else ()
        # param-dequant fold (decode_param_quant=int8): the pinned
        # pytree arrives as {"q": int8, "s": fp32} leaves and every
        # program dequantizes at COMPILE time
        pf = ((lambda p: dequantize_decode_params(p, cfg.dtype))
              if spec.param_quant == "int8" else (lambda p: p))
        # every program wraps in a FRESH lambda: jit caches key on the
        # function object, so jitting a shared module-level function
        # directly would pool every engine's compiled traces on one
        # handle and break per-engine one-trace accounting
        if quant:
            out.chunk = jax.jit(
                lambda params, kc, vc, ksc, vsc, bt, slot, toks, off, n:
                prefill_chunk_paged_q(cfg, pf(params), kc, vc, ksc, vsc,
                                      bt, slot, toks, off, n, t_logical=T),
                donate_argnums=donate)
            out.step = jax.jit(
                lambda params, kc, vc, ksc, vsc, bt, tok, pos, active:
                decode_step_paged_q(cfg, pf(params), kc, vc, ksc, vsc, bt,
                                    tok, pos, active, t_logical=T),
                donate_argnums=donate)
            if spec.spec_k:
                out.verify = jax.jit(
                    lambda params, kc, vc, ksc, vsc, bt, toks, pos,
                    active, nv:
                    verify_step_paged_q(cfg, pf(params), kc, vc, ksc, vsc,
                                        bt, toks, pos, active, nv,
                                        t_logical=T),
                    donate_argnums=donate)
            if spec.prefix:
                # the scale columns duplicate WITH the block: a CoW'd
                # block must dequantize identically to its src
                out.cow = jax.jit(
                    lambda kc, vc, ksc, vsc, src, dst:
                    cow_block_copy_q(kc, vc, ksc, vsc, src, dst),
                    donate_argnums=cow_donate)
        else:
            # block tables ride every call as DATA ([S, M] int32, fixed
            # shape): which blocks a slot owns never touches an aval, so
            # each program compiles once per engine config
            out.chunk = jax.jit(
                lambda params, kc, vc, bt, slot, toks, off, n:
                prefill_chunk_paged(cfg, pf(params), kc, vc, bt, slot,
                                    toks, off, n, t_logical=T),
                donate_argnums=donate)
            if spec.prefill_sp != "none":
                # tp=1 seqpar rides a ONE-device decode mesh: the
                # collectives degenerate (n=1) but the shard_map path is
                # genuinely exercised, and the chunk size equals the
                # budget so the math coincides with the single-lane
                # program exactly
                from ..topology import make_mesh

                sp_mesh = make_mesh((1,), axis_names=(DECODE_TP_AXIS,),
                                    devices=jax.devices()[:1])
                sp_backend = spec.prefill_sp
                out.chunk_sp = jax.jit(
                    lambda params, kc, vc, bt, slot, toks, off, n:
                    prefill_chunk_paged_sp(cfg, pf(params), kc, vc, bt,
                                           slot, toks, off, n, sp_mesh,
                                           sp_backend, t_logical=T,
                                           tp_axis=DECODE_TP_AXIS),
                    donate_argnums=donate)
            # the one-token step reads the live blocks in place where a
            # block is whole tiles on a TPU; every other program attends
            # the gathered view
            attend = paged_kernel.step_attention(pool_dtype,
                                                 spec.block_size, D)
            out.step = jax.jit(
                lambda params, kc, vc, bt, tok, pos, active:
                decode_step_paged(cfg, pf(params), kc, vc, bt, tok, pos,
                                  active, t_logical=T,
                                  paged_attention=attend),
                donate_argnums=donate)
            if spec.spec_k:
                # the fixed-K verify step: the [S, spec_k + 1] window is
                # the only static: ONE compiled trace serves every draft
                # mix and acceptance outcome
                out.verify = jax.jit(
                    lambda params, kc, vc, bt, toks, pos, active, nv:
                    verify_step_paged(cfg, pf(params), kc, vc, bt, toks,
                                      pos, active, nv, t_logical=T),
                    donate_argnums=donate)
            if spec.prefix:
                # copy-on-write: duplicate one block (both pools) before
                # a write lands in a shared one; src/dst traced scalars
                out.cow = jax.jit(
                    lambda kc, vc, src, dst: cow_block_copy(kc, vc, src,
                                                            dst),
                    donate_argnums=cow_donate)

    # -- KV transfer plane (disaggregated prefill/decode) --------------------
    # two programs, prefix-cache engines only: FETCH pulls one block's
    # slices off the pools (host-materialized into the wire payload),
    # SPLICE writes one received block into a freshly allocated pool
    # slot. The block id is a TRACED scalar in both: one compiled trace
    # each. Splice donates like the step/CoW; fetch cannot (the pools
    # survive it).
    if spec.prefix:
        out.fetch = jax.jit(
            lambda *a: tuple(
                jax.lax.dynamic_index_in_dim(pool, a[n], axis=1,
                                             keepdims=False)
                for pool in a[:n]))
        out.splice = jax.jit(
            lambda *a: tuple(
                jax.lax.dynamic_update_index_in_dim(pool, piece, a[n],
                                                    axis=1)
                for pool, piece in zip(a[:n], a[n + 1:])),
            donate_argnums=tuple(range(n)) if spec.donate else ())
    return out


def greedy_decode(cfg: TransformerConfig, params: Dict[str, Any],
                  tokens: jax.Array, lengths: jax.Array,
                  max_new: int, eos_id: Optional[int] = None) -> jax.Array:
    """Greedy continuation: up to ``max_new`` tokens per prompt.

    ``tokens`` [B, P] right-padded prompt ids, ``lengths`` [B] true prompt
    lengths (callers guarantee ``lengths + max_new <= cfg.max_seq``).
    Returns [B, max_new] generated ids. jit-able with static ``max_new``
    (the serving workload jits one instance per (B, P) shape bucket).

    With ``eos_id`` set, a lane that emits ``eos_id`` is FROZEN: later
    emissions are pad (0) and its ``pos`` stops advancing, so the lane
    stops widening the attention mask while the rest of the batch
    finishes — the batch still runs all ``max_new`` scan iterations
    (static shape), but finished lanes' output prefixes are bit-identical
    to the ``eos_id=None`` run up to and including the eos token.
    """
    B, P = tokens.shape
    # cache bound: positions can only ever reach P + max_new - 1 (callers
    # guarantee lengths <= P), so sizing the cache/attention to max_seq
    # would pay max_seq-width attention per generated token for nothing
    L, D, T = cfg.n_layers, cfg.d_model, P + max_new
    logits, ks, vs = prefill(cfg, params, tokens)
    k_cache = jnp.zeros((L, B, T, D), cfg.dtype).at[:, :, :P].set(ks)
    v_cache = jnp.zeros((L, B, T, D), cfg.dtype).at[:, :, :P].set(vs)
    # next token comes from each example's LAST REAL position, not slot P-1
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    first = jnp.argmax(last, axis=-1).astype(tokens.dtype)

    batch_ix = jnp.arange(B)

    def step(carry, _):
        k_cache, v_cache, pos, tok, done = carry
        h = (jnp.take(params["embed"], tok, axis=0)
             + jnp.take(params["pos"], pos, axis=0))
        for i in range(L):
            layer = jax.tree.map(lambda a: a[i], params["layers"])
            x = _rmsnorm(h, layer["ln1_g"])
            q, k, v = x @ layer["w_q"], x @ layer["w_k"], x @ layer["w_v"]
            k_cache = k_cache.at[i, batch_ix, pos].set(k)
            v_cache = v_cache.at[i, batch_ix, pos].set(v)
            h = h + _cached_attention(
                q, k_cache[i], v_cache[i], cfg.n_heads, pos) @ layer["w_o"]
            x = _rmsnorm(h, layer["ln2_g"])
            h = h + jax.nn.gelu(x @ layer["w_ff1"]) @ layer["w_ff2"]
        h = _rmsnorm(h, params["ln_f_g"])
        out = jnp.einsum("bd,vd->bv", h, params["embed"],
                         preferred_element_type=jnp.float32)
        nxt = jnp.argmax(out, axis=-1).astype(tok.dtype)
        # frozen lanes emit pad and stop paying attention width; live
        # lanes run the exact eos_id=None math (prefix-identical outputs)
        emit = jnp.where(done, jnp.zeros_like(nxt), nxt)
        new_done = done if eos_id is None else done | (emit == eos_id)
        new_pos = jnp.where(done, pos, pos + 1)
        return (k_cache, v_cache, new_pos, emit, new_done), emit

    if max_new <= 1:
        return first[:, None]
    done0 = (first == eos_id) if eos_id is not None else jnp.zeros(
        (B,), bool)
    _, rest = jax.lax.scan(
        step, (k_cache, v_cache, lengths, first, done0), None,
        length=max_new - 1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


class TransformerLM:
    """Trainer over a (worker, server) mesh: dp batches, tp weights."""

    def __init__(self, config: TransformerConfig, mesh=None,
                 dp_axis: str = WORKER_AXIS, tp_axis: str = SERVER_AXIS):
        from ..runtime import Session

        self.config = config
        self.mesh = mesh if mesh is not None else Session.get().mesh
        if config.d_model % config.n_heads != 0:
            Log.fatal("d_model must divide by n_heads")
        # Serving contract (mirrors TableBase): ``version`` counts train
        # steps; ``snapshot_params`` copies under the lock so the serving
        # layer never reads a params buffer a concurrent train step is
        # about to donate.
        import threading

        self._lock = threading.Lock()
        self.version = 0
        self._shardings = param_shardings(config, self.mesh, tp_axis)
        params = init_params(config)
        self.params = jax.tree.map(jax.device_put, params, self._shardings)
        self._momentum = jax.tree.map(
            lambda p, s: jax.device_put(jnp.zeros_like(p), s),
            self.params, self._shardings)
        batch_sharding = NamedSharding(self.mesh, P(dp_axis, None))

        cfg = config

        def train_step(params, mom, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, tokens))(params)
            mom = jax.tree.map(
                lambda m, g: cfg.momentum * m + g.astype(m.dtype), mom, grads)
            params = jax.tree.map(
                lambda p, m: p - cfg.learning_rate * m.astype(p.dtype),
                params, mom)
            return params, mom, loss

        self._step = jax.jit(
            train_step,
            in_shardings=(self._shardings, self._shardings, batch_sharding),
            out_shardings=(self._shardings, self._shardings, None),
            donate_argnums=(0, 1),
        )

    def train_batch(self, tokens: np.ndarray) -> jax.Array:
        """One dp+tp step on [B, T] token ids; returns async scalar loss."""
        with self._lock:
            self.params, self._momentum, loss = self._step(
                self.params, self._momentum, jnp.asarray(tokens, jnp.int32))
            self.version += 1
        return loss

    def snapshot_params(self) -> Tuple[Dict[str, Any], int]:
        """``(params copy, version)`` for the serving read path.

        The copies dispatch under the train lock — device-stream ordering
        guarantees they read the pre-donation buffers even while a train
        step races (the :meth:`tables.base.TableBase.snapshot_array`
        contract, for model params instead of a table).
        """
        with self._lock:
            return jax.tree.map(jnp.copy, self.params), self.version

    def logits(self, tokens: np.ndarray) -> jax.Array:
        return forward(self.config, self.params,
                       jnp.asarray(tokens, jnp.int32))

    def serving_programs(self, spec) -> Any:
        """The decode engine's seam: cache layout and paged programs
        (:func:`make_serving_programs`)."""
        return make_serving_programs(self.config, spec)
