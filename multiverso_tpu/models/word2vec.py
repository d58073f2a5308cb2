"""Word2vec (skip-gram / CBOW, negative sampling / hierarchical softmax).

TPU-native re-design of the reference WordEmbedding application's model core
(``Applications/WordEmbedding/src/wordembedding.cpp`` in the Multiverso
reference — ``FeedForward :57``, ``BPOutputLayer :74``, ``TrainSample :120``).
The reference trains scalar dot products in per-thread C++ loops against
row-cached parameters pulled from matrix tables. Here one jitted SPMD step
trains a whole batch of (center, target) pairs at once:

* embeddings are the tables' HBM-resident sharded arrays (input + output
  matrices — the same two tables the reference allocates,
  ``WE/src/communicator.cpp:17-33``), threaded through the step with donated
  buffers;
* negative sampling draws on-device from a unigram^0.75 alias table;
* gradients are closed-form (sigmoid loss), applied as row scatter-adds — the
  sparse "touched rows only" traffic the reference routes through the PS is
  the native dataflow of the gather/scatter pair;
* AdaGrad keeps full G-matrices like the reference's two AdaGrad tables
  (``communicator.cpp:17-33``), updated on the same touched rows;
* the batch is sharded over the ``worker`` mesh axis: XLA inserts the ICI
  collectives that replace worker->server delta pushes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops.embedding import scatter_add_rows
from ..topology import SERVER_AXIS, WORKER_AXIS

_ADAGRAD_EPS = 1e-8


def _dp_enter(key, tables):
    """Enter a ``dp_sync="dispatch"`` manual-worker region: advance the
    replicated key once (the dispatch's global stream), fold the worker
    index into the local draw key (decorrelated sampling per worker), and
    mark the table copies worker-varying so local training may diverge
    until :func:`_dp_exchange`. Returns (local_key, key_out, tables)."""
    key_out = jax.random.split(key)[0]
    lkey = jax.random.fold_in(key, jax.lax.axis_index(WORKER_AXIS))
    varying = tuple(
        None if a is None
        else jax.lax.pcast(a, (WORKER_AXIS,), to="varying")
        for a in tables)
    return lkey, key_out, varying


def _keyed_exchange_one(a, a0, cap: int):
    """Dirty-row-union delta exchange for ONE table (exact).

    The dense exchange psums the full ``[V, D]`` delta — fine over ICI,
    ruinous over DCN (~57 MB/table at the real 71k x 200 shape). The
    reference's cross-host Adds already send only touched rows
    (``src/table/sparse_matrix_table.cpp:145-153`` in the Multiverso
    reference); this is the jitted SPMD form of that:

    1. psum a ``[V]`` row-moved mask over the worker axis (V*4 wire) —
       its result is REPLICATED, so every worker derives the identical
       fixed-size index list and the row psum below is row-aligned;
    2. gather the first ``cap`` union rows of the local delta (static
       shape; absent rows gather 0) and psum just those (cap*D*4 wire);
    3. scatter-add the summed rows onto the saved base.

    Exact whenever the union fits the cap; an overflow falls back to
    the dense psum INSIDE the dispatch (`lax.cond` on the replicated
    count — every worker takes the same branch, so the collective
    stays uniform) — never silently drops movement. Wire per table:
    ``V*4 + cap*D*4`` vs dense ``V*D*4``.
    """
    V = a.shape[0]
    delta = a - a0
    moved = jnp.any(delta != 0, axis=1)
    union = jax.lax.psum(moved.astype(jnp.float32), WORKER_AXIS) > 0
    n_dirty = jnp.sum(union.astype(jnp.int32))
    (idx,) = jnp.where(union, size=min(cap, V), fill_value=V)
    rows = jnp.take(delta, idx, axis=0, mode="fill", fill_value=0)
    summed = jax.lax.psum(rows, WORKER_AXIS)
    return jax.lax.cond(
        n_dirty <= idx.shape[0],
        lambda: a0.at[idx].add(summed.astype(a0.dtype), mode="drop"),
        lambda: a0 + jax.lax.psum(delta, WORKER_AXIS))


def _dp_exchange(tables, saved, mode: str = "dense", cap: int = 0):
    """ONE summed-delta exchange per dispatch: ``a0 + psum(a - a0)``
    (``mode="dense"``) or the dirty-row-union keyed form
    (``mode="keyed"``, :func:`_keyed_exchange_one`). Both are exact for
    commutative updaters (the Sigma-invariant); this is the only wire
    traffic of the dispatch-mode dp data plane (docs/DISTRIBUTED.md
    "Bytes on the wire")."""
    if mode == "keyed":
        return tuple(
            None if a0 is None else _keyed_exchange_one(a, a0, cap)
            for a, a0 in zip(tables, saved))
    return tuple(
        None if a0 is None else a0 + jax.lax.psum(a - a0, WORKER_AXIS)
        for a, a0 in zip(tables, saved))


@dataclass
class Word2VecConfig:
    """Mirrors the reference CLI options (``WE/src/util.cpp`` Option)."""

    vocab_size: int = 0
    embedding_size: int = 100
    window: int = 5
    negative: int = 5            # 0 + hs=True -> hierarchical softmax only
    hs: bool = False
    cbow: bool = False
    init_lr: float = 0.025
    min_lr_frac: float = 1e-4    # lr floor = init_lr * frac (reference :38-56)
    use_adagrad: bool = False
    batch_size: int = 1024
    steps_per_call: int = 1      # batches fused into one dispatch (lax.scan)
    max_code_length: int = 40    # huffman path pad (HS)
    seed: int = 7
    # Device-sampler candidate oversampling (corpus path only). Window /
    # sentence / subsampling tests reject ~half the sampled pairs; with
    # oversample > 1 the sampler draws ``oversample * batch_size`` cheap
    # int candidates and compacts the survivors into a dense batch, so the
    # expensive per-row gather/scatter work runs at ~full utilisation.
    # 0 disables (every candidate slot trains with a validity mask).
    oversample: float = 0.0
    # > 0 enables the pre-drawn negative pool for the device-corpus path
    # (see build_negative_pool); the pool is grown to at least twice the
    # draws per fused call. 0 = exact per-draw alias sampling.
    neg_pool_size: int = 0
    # group size G > 1 shares each K-negative draw across G consecutive
    # pairs, cutting the dominant negative-row gather/scatter traffic by G
    # (same objective in expectation; 0/1 = exact per-pair draws, the
    # reference semantics). Requires batch_size % G == 0.
    shared_negatives: int = 0
    # normalize each row's summed batch gradient by the row's occurrence
    # count before applying lr. The reference applies pairs SEQUENTIALLY
    # (one lr-scaled update per pair); a batched scatter SUMS colliding
    # pair grads, so hot (frequent) rows receive thousands-of-pairs-sized
    # steps and TRAINING DIVERGES once hot rows collect enough colliding
    # grads (zipf head words at 64k batch NaN within one dispatch — vocab
    # SIZE is not what matters, hot-row mass is). Enable for large batches;
    # None = auto: the train() driver estimates the hottest row's expected
    # hits from the sampling laws and enables it past ~512 (stable ~150,
    # divergent ~2300); False = reference-equivalent sum always. Falsy
    # when a Word2Vec is built directly without resolution.
    row_mean_updates: Optional[bool] = None
    # with row_mean_updates: use a STATIC expected-count scale table
    # (computed once per corpus chunk from the sampling laws — subsampled
    # unigram for centers/contexts, unigram^0.75 for negatives) instead of
    # realized per-step counts. Saves the per-step [V] counts scatter
    # (~12% of the stabilised step at the bench shape). Expectation ==
    # realization for the hot rows the cap exists for (CV = 1/sqrt(hits));
    # cold rows scale to 1 either way. Device-corpus path only (the
    # expected laws come from load_corpus_chunk); requires plain SGD,
    # skip-gram, no HS, and oversample > 1 (validated at construction).
    row_mean_static: bool = False
    # with row_mean_updates: per-row update = mean-grad * min(count, cap).
    # cap bounds how much a hot row can move per batch — rows with <= cap
    # collisions keep the reference's sequential-sum movement exactly;
    # hotter rows are clamped to cap pair-steps (the sigmoid saturation
    # that self-limits the reference's sequential loop has no batched
    # equivalent, so the cap plays that role). cap=1 -> pure mean.
    row_update_cap: float = 8.0
    # Cross-worker exchange cadence for in-mesh data parallelism (worker
    # axis > 1). The reference never ships a dense table on the wire (its
    # sync Adds are sparse-filtered row buckets,
    # ``src/table/sparse_matrix_table.cpp:145-153``); per-batch GSPMD BSP
    # on replicated tables does — a table-sized allreduce EVERY scan
    # iteration (43-57% measured overhead, docs/DISTRIBUTED.md).
    #   "dispatch" — workers train their batch shards LOCALLY within one
    #                fused dispatch (each sees its own updates immediately,
    #                peers' at dispatch boundaries — the async-PS staleness
    #                model, bounded by steps_per_call) and exchange ONE
    #                summed table delta per dispatch:
    #                ``w = w0 + psum(w_local - w0)``. Sigma-invariant exact
    #                for commutative updaters; wire bytes cut ~3*S vs
    #                per-batch BSP.
    #   "batch"    — per-batch BSP via GSPMD (exact per-batch freshness at
    #                S x the wire cost).
    # Falls back to "batch" when batch_size doesn't divide over the
    # worker axis (and shared-negative groups).
    dp_sync: str = "dispatch"
    # dp_sync="dispatch" exchange wire format:
    #   "dense" — ONE fused psum of the full table deltas. Right for
    #             in-mesh ICI, where a 57 MB/table allreduce is sub-ms.
    #   "keyed" — dirty-row union over the worker axis: psum a [V]
    #             row-moved mask, exchange only the first dp_keyed_cap
    #             union rows (fixed shape), exact dense fallback inside
    #             the dispatch when the union overflows the cap. Right
    #             for the cross-HOST (DCN) mesh: wire per table is
    #             V*4 + cap*D*4 vs V*D*4 dense — measured >=5x smaller
    #             at the real 71k x 200 shape with per-batch dispatches
    #             (docs/DISTRIBUTED.md "Bytes on the wire"). Size the
    #             cap just above the per-dispatch touched-row union
    #             (zipf B=8k batches measure ~6.5k; overflow only costs
    #             a dense-rate dispatch, never correctness).
    dp_exchange: str = "dense"
    dp_keyed_cap: int = 0        # 0 = auto: vocab // 4


def build_unigram_alias(counts: np.ndarray, power: float = 0.75
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Alias tables for O(1) unigram^0.75 negative sampling.

    Replaces the reference's precomputed 1e8-slot sampling table
    (``WE/src/util.cpp`` Sampler) with the alias method: two O(vocab) arrays,
    sampled on device with two uniforms.
    """
    probs = counts.astype(np.float64) ** power
    probs /= probs.sum()
    n = probs.shape[0]
    scaled = probs * n
    alias = np.zeros(n, np.int32)
    thresh = np.ones(n, np.float32)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        thresh[s] = scaled[s]
        alias[s] = l
        scaled[l] = scaled[l] - (1.0 - scaled[s])
        (small if scaled[l] < 1.0 else large).append(l)
    for i in large + small:
        thresh[i] = 1.0
        alias[i] = i
    return thresh, alias


def pack_alias_table(thresh: jax.Array, alias: jax.Array) -> jax.Array:
    """Pack thresh/alias into one [V, 2] i32 table so a draw costs a single
    2-wide row gather instead of two scalar gathers (scalar gathers are the
    slow path on TPU).  Build once; :func:`sample_negatives` takes the result.
    """
    return jnp.stack(
        [jax.lax.bitcast_convert_type(thresh, jnp.int32), alias], axis=1)


def sample_negatives(rng_key, packed: jax.Array,
                     shape: Tuple[int, ...]) -> jax.Array:
    """Draw indices from a packed alias table (:func:`pack_alias_table`)."""
    n = packed.shape[0]
    k1, k2 = jax.random.split(rng_key)
    idx = jax.random.randint(k1, shape, 0, n)
    u = jax.random.uniform(k2, shape)
    row = jnp.take(packed, idx, axis=0)                     # [..., 2]
    t = jax.lax.bitcast_convert_type(row[..., 0], jnp.float32)
    return jnp.where(u < t, idx, row[..., 1])


def build_negative_pool(thresh: np.ndarray, alias: np.ndarray, size: int,
                        seed: int = 0) -> np.ndarray:
    """Pre-draw ``size`` unigram^0.75 samples on the host (vectorised alias).

    The device-resident pool is the TPU form of the reference's precomputed
    1e8-slot sampling table (``WE/src/util.cpp`` Sampler): drawing K
    negatives becomes one random offset + a contiguous ``dynamic_slice``
    instead of K random gathers — random gathers are the slow path on TPU
    (measured ~20%% of the fused step at batch 32k x 5 negatives).
    """
    rng = np.random.default_rng(seed)
    n = thresh.shape[0]
    idx = rng.integers(0, n, size).astype(np.int32)
    u = rng.random(size).astype(np.float32)
    return np.where(u < thresh[idx], idx, alias[idx]).astype(np.int32)


def pool_negatives(rng_key, pool: jax.Array,
                   shape: Tuple[int, ...]) -> jax.Array:
    """Take ``prod(shape)`` consecutive pool entries at a random offset."""
    n = int(np.prod(shape))
    start = jax.random.randint(rng_key, (), 0, pool.shape[0] - n + 1)
    return jax.lax.dynamic_slice(pool, (start,), (n,)).reshape(shape)


def pack_survivors(ok: jax.Array, n_slots: int, *arrays: jax.Array):
    """Pack the ``ok`` rows of each ``[M, ...]`` array into ``[n_slots,
    ...]``, in their order: ``(*packed, valid)``, slot b holding the
    b-th survivor, the slots past the survivors zero and ``valid`` false
    there; survivors past ``n_slots`` are left out.

    ONE sort of the candidates by a unique key (a survivor's position,
    anyone else's position + M), so it need not be stable; 1-D arrays
    ride along as payloads, wider ones are gathered by the sorted
    position, which rides along only for them. Priced on a TPU v5e at the
    benchmark cell's 163,840 candidates into 65,536 slots, two id arrays
    (tools/w2v_kernel_probe.py ``compact.*``, PR 33): this sort 0.246
    ms, each survivor scattered to its prefix-count rank 1.539 (a narrow
    scatter costs 9.4 ns a candidate and array); a binary search over the
    survivor prefix-sum plus row gathers was 2.2x slower than that
    scatter end to end on an earlier stack."""
    M = ok.shape[0]
    pos = jnp.arange(M, dtype=jnp.int32)
    flat = [a for a in arrays if a.ndim == 1]
    wide = len(flat) < len(arrays)
    out = jax.lax.sort(
        (jnp.where(ok, pos, M + pos),) + (pos,) * wide + tuple(flat),
        num_keys=1, is_stable=False)
    rode = iter(out[1 + wide:])
    rows = [next(rode)[:n_slots] if a.ndim == 1 else a[out[1][:n_slots]]
            for a in arrays]
    valid = jnp.arange(n_slots) < ok.sum()
    return tuple(
        jnp.where(valid.reshape((n_slots,) + (1,) * (r.ndim - 1)), r,
                  jnp.zeros((), r.dtype))
        for r in rows) + (valid,)


class Word2Vec:
    """Jitted trainer bound to input/output embedding tables."""

    def __init__(self, config: Word2VecConfig, input_table, output_table,
                 counts: Optional[np.ndarray] = None,
                 huffman: Optional["HuffmanCodes"] = None) -> None:
        if config.vocab_size <= 0:
            config.vocab_size = input_table.num_row
        self.config = config
        self.input_table = input_table
        self.output_table = output_table
        from jax.sharding import NamedSharding, PartitionSpec as P

        # Replicated-committed key: keeps the key's sharding identical between
        # the first call (host-created) and later calls (jit output), so the
        # step never retraces on a sharding change.
        self._key_sharding = NamedSharding(input_table.mesh, P())
        self._key = jax.device_put(jax.random.PRNGKey(config.seed),
                                   self._key_sharding)
        if config.negative <= 0 and not config.hs:
            Log.fatal("word2vec needs an output objective: negative > 0 "
                      "and/or hs=True")
        if (config.shared_negatives > 1
                and config.batch_size % config.shared_negatives != 0):
            Log.fatal("batch_size must divide by shared_negatives group")
        self._host_counts = (None if counts is None
                             else np.asarray(counts, np.float64))
        if config.row_mean_updates and config.row_mean_static:
            # Static scales only model what they can predict: word-law
            # expectations for full, compacted skip-gram batches.
            if counts is None:
                Log.fatal("row_mean_static requires vocab counts")
            if config.use_adagrad:
                Log.fatal("row_mean_static supports plain SGD only")
            if config.hs:
                # HS scatters Huffman NODE ids; the word-law table would
                # look up unrelated words and leave the hottest rows
                # (top tree nodes) uncapped. Realized counts handle HS.
                Log.fatal("row_mean_static does not support hierarchical "
                          "softmax (use realized counts)")
            if config.cbow:
                Log.fatal("row_mean_static supports skip-gram only")
            if config.oversample <= 1:
                # without candidate compaction only ~half the batch slots
                # hold valid pairs, so the full-B expectations over-cap
                # hot rows ~2x; compaction makes B the realized count
                Log.fatal("row_mean_static requires oversample > 1 "
                          "(compacted full batches make the expected "
                          "counts match realizations)")
        if config.negative > 0:
            if counts is None:
                Log.fatal("negative sampling requires vocab counts")
            # Only the packed [V, 2] table is kept on device; the separate
            # thresh/alias arrays stay host-side (numpy) for pool building.
            thresh, alias = build_unigram_alias(counts)
            self._packed_alias = pack_alias_table(jnp.asarray(thresh),
                                                  jnp.asarray(alias))
            self._host_thresh, self._host_alias = thresh, alias
            self._neg_pool = None
        if config.hs:
            if huffman is None:
                Log.fatal("hierarchical softmax requires huffman codes")
            self._paths = jnp.asarray(huffman.paths)       # [vocab, L]
            self._codes = jnp.asarray(huffman.codes)       # [vocab, L]
            self._path_mask = jnp.asarray(huffman.mask)    # [vocab, L]
        if config.use_adagrad:
            # physical table shape: G rows align 1:1 with (padded) embedding
            # rows so the scatter-accumulate shares the table's sharding
            shape = input_table.padded_shape
            zeros = lambda: jax.jit(
                lambda: jnp.zeros(shape, jnp.float32),
                out_shardings=input_table.sharding)()
            self._g_in = zeros()
            self._g_out = zeros()
        self._static_scale_in = None   # set by load_corpus_chunk when
        self._static_scale_out = None  # cfg.row_mean_static
        self._step = self._build_step()
        self._words_trained = 0.0  # corpus WORDS (not pairs) — see current_lr
        self.total_words = 0       # set by the driver for lr decay
        # device-corpus stream cursor (position of the next candidate slab);
        # persists across chunk loads so rotation continues seamlessly —
        # see set_stream_pos for the multi-process partition hook
        self._stream_pos = 0

    # -- lr schedule (reference UpdateLearningRate, wordembedding.cpp:38) --
    def current_lr(self) -> float:
        """Linear decay over corpus words, floored at ``min_lr_frac``.

        Both ``total_words`` and the trained counter are in WORD units
        (``word_count_actual`` in the reference). Batch calls advance the
        counter by ``pairs / (window + 1)`` — the expected pairs per word
        under random window shrink — unless the driver keeps it exact via
        ``set_words_trained``.
        """
        cfg = self.config
        if cfg.use_adagrad or self.total_words <= 0:
            return cfg.init_lr
        frac = 1.0 - self._words_trained / (self.total_words + 1)
        return cfg.init_lr * max(frac, cfg.min_lr_frac)

    def set_words_trained(self, words: float) -> None:
        """Exact progress hook for drivers that track corpus words."""
        self._words_trained = float(words)

    def set_stream_pos(self, pos: int) -> None:
        """Place the device-corpus stream cursor (API contract for the
        multi-process data partition: each process streams its own arc of
        the cyclic chunk, so drivers offset the cursor per rank)."""
        self._stream_pos = int(pos)

    def _pairs_to_words(self, pairs: float) -> float:
        return pairs / (self.config.window + 1)

    def _dp_local(self) -> int:
        """Worker-axis size of the local-accumulation dp exchange (1 = off).

        > 1 means the multi-batch/corpus dispatches run under shard_map
        with the worker axis MANUAL: each worker trains its batch shard
        against a local table copy and the dispatch exchanges one summed
        delta (``dp_sync="dispatch"``). The server axis stays AUTO, so
        server-sharded tables keep their GSPMD layout inside.
        """
        cfg = self.config
        dp = int(self.input_table.mesh.shape[WORKER_AXIS])
        if dp <= 1 or cfg.dp_sync != "dispatch":
            return 1
        G = max(int(cfg.shared_negatives), 1)
        if cfg.batch_size % dp != 0 or (cfg.batch_size // dp) % G != 0:
            if not getattr(self, "_dp_fallback_logged", False):
                self._dp_fallback_logged = True
                Log.info(
                    "dp_sync=dispatch needs batch_size divisible over "
                    "%d workers (and G=%d groups); falling back to "
                    "per-batch GSPMD sync", dp, G)
            return 1
        return dp

    def _keyed_cap(self) -> int:
        """Static row cap of the ``dp_exchange="keyed"`` wire format
        (ignored for dense). Auto (0) = vocab // 4 — comfortably above
        the measured per-dispatch touched-row union for zipf corpora at
        per-batch dispatches (docs/DISTRIBUTED.md), while still 3-4x
        less wire than dense; overflow costs one dense-rate dispatch,
        never correctness."""
        cfg = self.config
        if cfg.dp_exchange not in ("dense", "keyed"):
            Log.fatal(f"unknown dp_exchange {cfg.dp_exchange!r} "
                      "(expected 'dense' or 'keyed')")
        if int(cfg.dp_keyed_cap) > 0:
            return int(cfg.dp_keyed_cap)
        return max(256, cfg.vocab_size // 4)

    # -- jitted step -------------------------------------------------------
    def _build_step(self):
        cfg = self.config
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.input_table.mesh
        batch_sharding = NamedSharding(mesh, P(WORKER_AXIS))
        emb_sharding = self.input_table.sharding

        def apply_sgd(w, rows, grads, lr, scale=None):
            upd = -lr * grads if scale is None \
                else (-lr) * scale[:, None] * grads
            return scatter_add_rows(w, rows, upd)

        def apply_adagrad(w, g_acc, rows, grads, lr):
            g_rows = jnp.take(g_acc, rows, axis=0) + grads * grads
            g_acc = g_acc.at[rows].add(grads * grads)
            scale = lr / jnp.sqrt(g_rows + _ADAGRAD_EPS)
            return w.at[rows].add((-scale * grads).astype(w.dtype)), g_acc

        D = cfg.embedding_size

        def objective_grads(h, w_out, target_word, ex_mask, key, negs=None):
            """Shared output-side objectives on hidden vector ``h`` [B, D].

            Negative sampling and hierarchical softmax are ADDITIVE when both
            are enabled (matching the reference trainer, which runs both
            branches per sample when hs=1 and negative>0). Returns the summed
            loss, grad wrt h, and the (rows, grads) scatter sets for w_out.
            ``negs`` lets the corpus path pass bulk-predrawn negatives
            (hoisting the alias draws out of the scan body).
            """
            loss = 0.0
            # f32 accumulation regardless of table dtype (bf16 tables keep
            # the MXU/HBM win; grads stay f32 until the scatter cast)
            grad_h = jnp.zeros(h.shape, jnp.float32)
            scatters = []
            G = max(int(cfg.shared_negatives), 1)
            if cfg.negative > 0:
                # ONE implementation for exact and group-shared sampling:
                # G = 1 draws K negatives per pair (reference semantics);
                # G > 1 shares each K-draw across G consecutive pairs,
                # cutting the dominant [*, K, D] gather/scatter traffic by
                # G (the step is HBM-bound on target rows — see bench; same
                # objective in expectation).
                B = h.shape[0]
                if negs is None:
                    key, sub = jax.random.split(key)
                    negs = sample_negatives(sub, self._packed_alias,
                                            (B // G, cfg.negative))
                # positive pairs (always exact, per pair)
                u_pos = jnp.take(w_out, target_word, axis=0)     # [B, D]
                s_pos = jnp.clip(
                    jnp.einsum("bd,bd->b", h, u_pos,
                               preferred_element_type=jnp.float32),
                    -30.0, 30.0)
                g_pos = (jax.nn.sigmoid(s_pos) - 1.0) * ex_mask
                loss = loss + ((jax.nn.softplus(s_pos) - s_pos)
                               * ex_mask).sum()
                grad_h = grad_h + g_pos[:, None] * u_pos
                # scatter-bound grads are emitted in the TABLE dtype when
                # that is rounding-equivalent: the plain-SGD scatter converts
                # each update to it before adding anyway, and a bf16 [N, D]
                # buffer halves the dominant HBM traffic of the update path.
                # NOT equivalent for AdaGrad (consumes grads in f32 math),
                # shared negatives (G-group contraction must accumulate
                # f32), or row-mean (the per-row scale multiplies AFTER,
                # which would double-round).
                exact_cast = (not cfg.use_adagrad and G == 1
                              and not cfg.row_mean_updates)
                scat_dt = w_out.dtype if exact_cast else jnp.float32
                scatters.append((target_word,
                                 (g_pos[:, None] * h).astype(scat_dt),
                                 ex_mask))
                # negatives: [B/G, K, D] rows (per-pair when G == 1)
                u_neg = jnp.take(w_out, negs, axis=0)            # [B/G, K, D]
                hg = h.reshape(B // G, G, D)
                mg = ex_mask.reshape(B // G, G)
                s_neg = jnp.clip(
                    jnp.einsum("gbd,gkd->gbk", hg, u_neg,
                               preferred_element_type=jnp.float32),
                    -30.0, 30.0)
                g_neg = jax.nn.sigmoid(s_neg) * mg[:, :, None]
                loss = loss + (jax.nn.softplus(s_neg)
                               * mg[:, :, None]).sum()
                grad_h = grad_h + jnp.einsum(
                    "gbk,gkd->gbd", g_neg, u_neg,
                    preferred_element_type=jnp.float32).reshape(B, D)
                # each negative slot's grad is summed over its group's valid
                # pairs, so its occurrence weight is the valid-pair COUNT
                # (a binary flag would under-divide hot rows by up to G)
                occ_neg = jnp.broadcast_to(
                    mg.sum(axis=1)[:, None],
                    (B // G, cfg.negative)).reshape(-1)
                scatters.append((negs.reshape(-1), jnp.einsum(
                    "gbk,gbd->gkd", g_neg, hg,
                    preferred_element_type=scat_dt).reshape(-1, D),
                    occ_neg))
            if cfg.hs:
                nodes = jnp.take(self._paths, target_word, axis=0)   # [B, L]
                codes = jnp.take(self._codes, target_word, axis=0)
                pmask = jnp.take(self._path_mask, target_word, axis=0)
                labels = (1.0 - codes)
                u = jnp.take(w_out, nodes, axis=0)
                scores = jnp.clip(
                    jnp.einsum("bd,bld->bl", h, u,
                               preferred_element_type=jnp.float32),
                    -30.0, 30.0)
                g = (jax.nn.sigmoid(scores) - labels) * pmask * ex_mask[:, None]
                path_loss = (jax.nn.softplus(scores) - labels * scores) * pmask
                loss = loss + (path_loss.sum(1) * ex_mask).sum()
                grad_h = grad_h + jnp.einsum(
                    "bl,bld->bd", g, u, preferred_element_type=jnp.float32)
                scatters.append((nodes.reshape(-1),
                                 (g[:, :, None] * h[:, None, :]).reshape(-1, D),
                                 (pmask * ex_mask[:, None]).reshape(-1)))
            loss = loss / jnp.maximum(ex_mask.sum(), 1)
            return loss, grad_h, scatters, key

        def _row_counts(sets):
            """Per-row contribution counts summed over ALL scatter sets of
            one table (a single joint count keeps the cap a per-table bound
            — per-set counts would let a row move n_sets * cap pair-steps
            when it appears in several sets, e.g. as positive target AND
            shared negative)."""
            counts = jnp.zeros((cfg.vocab_size,), jnp.float32)
            for rows, occ in sets:
                counts = counts.at[rows].add(occ, mode="drop")
            return counts

        def _row_scale(counts, rows, grads):
            """Rescale a row's summed grads to ``mean * min(count, cap)``.

            ``occ``/counts weight masked/padded slots as 0 (compaction's
            row-0 filler doesn't dilute row 0; shared-negative slots carry
            their group's valid-pair count). The counts pass is [N]+[V]-
            sized — negligible next to the [N, D] grads themselves.
            """
            cap = max(float(cfg.row_update_cap), 1.0)
            c = jnp.maximum(jnp.take(counts, rows, axis=0), 1.0)
            return grads * (jnp.minimum(c, cap) / c)[:, None]

        def _row_scale_vec(counts, rows):
            """[N] multiplier form of ``_row_scale`` — handed to apply_sgd
            so the rescale fuses into the scatter operand's elementwise
            chain instead of materialising a second [N, D] grads pass
            (measured ~35%% of the step at the bench shape)."""
            cap = max(float(cfg.row_update_cap), 1.0)
            c = jnp.maximum(jnp.take(counts, rows, axis=0), 1.0)
            return jnp.minimum(c, cap) / c

        def _static_scales(in_rows, scatters):
            """Expected-count scale lookup (row_mean_static): one [N]
            gather from a per-chunk static table instead of the realized
            [V] counts scatter."""
            if self._static_scale_in is None:
                Log.fatal("row_mean_static needs the expected-count tables "
                          "from load_corpus_chunk (device-corpus path)")
            in_scale = jnp.take(self._static_scale_in, in_rows, axis=0)
            out_scales = [jnp.take(self._static_scale_out, rows, axis=0)
                          for rows, _, _ in scatters]
            return in_scale, out_scales

        def apply_updates(w_in, w_out, g_in, g_out, in_rows, in_grads,
                          in_occ, scatters, lr):
            in_scale = out_counts = None
            out_scales = None
            if cfg.row_mean_updates and cfg.row_mean_static:
                # (sgd-only, validated in __init__)
                in_scale, out_scales = _static_scales(in_rows, scatters)
            elif cfg.row_mean_updates:
                in_counts = _row_counts([(in_rows, in_occ)])
                out_counts = _row_counts(
                    [(rows, occ) for rows, _, occ in scatters])
                if cfg.use_adagrad:
                    # adagrad consumes scaled grads twice (G accumulation +
                    # update): materialise once
                    in_grads = _row_scale(in_counts, in_rows, in_grads)
                    scatters = [
                        (rows, _row_scale(out_counts, rows, grads), occ)
                        for rows, grads, occ in scatters]
                else:
                    in_scale = _row_scale_vec(in_counts, in_rows)
            if cfg.use_adagrad:
                w_in, g_in = apply_adagrad(w_in, g_in, in_rows, in_grads, lr)
                for rows, grads, _ in scatters:
                    w_out, g_out = apply_adagrad(w_out, g_out, rows, grads, lr)
            else:
                w_in = apply_sgd(w_in, in_rows, in_grads, lr, in_scale)
                for i, (rows, grads, _) in enumerate(scatters):
                    if out_scales is not None:
                        scale = out_scales[i]
                    else:
                        scale = (None if out_counts is None
                                 else _row_scale_vec(out_counts, rows))
                    w_out = apply_sgd(w_out, rows, grads, lr, scale)
            return w_in, w_out, g_in, g_out

        if not cfg.cbow:
            # skip-gram: input row = center word; target = context word
            def step(w_in, w_out, g_in, g_out, centers, contexts, mask, lr,
                     key, negs=None):
                h = jnp.take(w_in, centers, axis=0)
                loss, grad_h, scatters, key = objective_grads(
                    h, w_out, contexts, mask, key, negs)
                w_in, w_out, g_in, g_out = apply_updates(
                    w_in, w_out, g_in, g_out, centers, grad_h, mask,
                    scatters, lr)
                return w_in, w_out, g_in, g_out, loss, key
        else:
            # CBOW: input = mean of context window rows; target = center word
            # (reference TrainSample CBOW path; contexts [B, C] with cmask)
            def step(w_in, w_out, g_in, g_out, centers, contexts, cmask, lr,
                     key, negs=None):
                rows = jnp.take(w_in, contexts, axis=0)          # [B, C, D]
                counts = jnp.maximum(cmask.sum(axis=1), 1.0)     # [B]
                h = jnp.einsum("bcd,bc->bd", rows, cmask) / counts[:, None]
                ex_mask = (cmask.sum(axis=1) > 0).astype(jnp.float32)
                loss, grad_h, scatters, key = objective_grads(
                    h, w_out, centers, ex_mask, key, negs)
                # d h / d row_c = cmask_c / count
                in_grads = (grad_h[:, None, :]
                            * (cmask / counts[:, None])[:, :, None])
                w_in, w_out, g_in, g_out = apply_updates(
                    w_in, w_out, g_in, g_out, contexts.reshape(-1),
                    in_grads.reshape(-1, D), cmask.reshape(-1), scatters, lr)
                return w_in, w_out, g_in, g_out, loss, key

        state_shardings = (emb_sharding, emb_sharding,
                           emb_sharding if cfg.use_adagrad else None,
                           emb_sharding if cfg.use_adagrad else None)

        def multi_step(w_in, w_out, g_in, g_out, centers, contexts, mask,
                       lr, key):
            """Scan ``steps_per_call`` batches in one dispatch: amortises
            host->device dispatch latency (batches stacked on axis 0)."""

            def body(carry, xs):
                w_in, w_out, g_in, g_out, key = carry
                c, t, m = xs
                w_in, w_out, g_in, g_out, loss, key = step(
                    w_in, w_out, g_in, g_out, c, t, m, lr, key)
                return (w_in, w_out, g_in, g_out, key), loss

            (w_in, w_out, g_in, g_out, key), losses = jax.lax.scan(
                body, (w_in, w_out, g_in, g_out, key),
                (centers, contexts, mask))
            return w_in, w_out, g_in, g_out, losses.mean(), key

        dp = self._dp_local()

        def multi_step_local(w_in, w_out, g_in, g_out, centers, contexts,
                             mask, lr, key):
            """``dp_sync="dispatch"``: each worker scans its batch shards
            against a LOCAL table copy (zero collectives in the loop) and
            the dispatch ends with ONE summed-delta exchange —
            ``w = w0 + psum(w_local - w0)``. Runs under shard_map with the
            worker axis manual; the server axis stays auto, so GSPMD still
            lays the table math out over server shards. Wire bytes per
            dispatch: 2 tables once, vs (2-3 tables) x steps_per_call for
            per-batch BSP (docs/DISTRIBUTED.md has the accounting)."""
            saved = (w_in, w_out, g_in, g_out)
            lkey, key_out, (w_in, w_out, g_in, g_out) = _dp_enter(key, saved)

            def body(carry, xs):
                w_in, w_out, g_in, g_out, key = carry
                c, t, m = xs
                w_in, w_out, g_in, g_out, loss, key = step(
                    w_in, w_out, g_in, g_out, c, t, m, lr, key)
                return (w_in, w_out, g_in, g_out, key), loss

            (w_in, w_out, g_in, g_out, _), losses = jax.lax.scan(
                body, (w_in, w_out, g_in, g_out, lkey),
                (centers, contexts, mask))

            w_in, w_out, g_in, g_out = _dp_exchange(
                (w_in, w_out, g_in, g_out), saved,
                mode=cfg.dp_exchange, cap=self._keyed_cap())
            loss = jax.lax.psum(losses.mean(), WORKER_AXIS) / dp
            return w_in, w_out, g_in, g_out, loss, key_out

        if dp > 1:
            sm_batch = (P(None, WORKER_AXIS) if not cfg.cbow
                        else P(None, WORKER_AXIS, None))
            multi_step = jax.shard_map(
                multi_step_local, mesh=mesh,
                in_specs=(P(), P(), P(), P(),
                          P(None, WORKER_AXIS), sm_batch, sm_batch,
                          P(), P()),
                out_specs=(P(), P(), P(), P(), P(), P()),
                axis_names={WORKER_AXIS})

        multi_batch_sharding = NamedSharding(mesh, P(None, WORKER_AXIS))
        key_sharding = self._key_sharding
        jitted = jax.jit(
            step,
            donate_argnums=(0, 1, 2, 3),
            in_shardings=state_shardings + (batch_sharding,) * 3
            + (None, key_sharding),
            out_shardings=state_shardings + (None, key_sharding),
        )
        self._multi_step = jax.jit(
            multi_step,
            donate_argnums=(0, 1, 2, 3),
            in_shardings=state_shardings + (multi_batch_sharding,) * 3
            + (None, key_sharding),
            out_shardings=state_shardings + (None, key_sharding),
        )
        self._raw_step = step
        self._state_shardings = state_shardings
        return jitted

    def _ensure_neg_pool(self, n_draws: int) -> jax.Array:
        """Device pool with at least ``2 * n_draws`` pre-drawn negatives."""
        need = max(int(self.config.neg_pool_size), 2 * n_draws)
        if self._neg_pool is None or self._neg_pool.shape[0] < 2 * n_draws:
            pool = build_negative_pool(self._host_thresh, self._host_alias,
                                       need, seed=self.config.seed + 1)
            self._neg_pool = jnp.asarray(pool)
        return self._neg_pool

    def _candidate_batch(self, n: int) -> int:
        """GLOBAL candidate slab length M for a corpus chunk of ``n``
        positions (candidates consumed per fused step, summed over the
        worker axis — ``dp_sync="dispatch"`` gives each worker its own
        ``M // dp`` slab on its own arc of the chunk).

        Single source of truth for the oversample formula — the device
        sampler and the host-side stream-position bookkeeping must agree.
        Clamped so ``ext`` slicing (n >= M_local + 2W) stays in bounds.
        """
        cfg = self.config
        B, W = cfg.batch_size, cfg.window
        dp = self._dp_local()
        Bl = B // dp
        if n < Bl + 2 * W:
            Log.fatal(f"corpus chunk ({n} positions) smaller than "
                      f"per-worker batch + 2*window ({Bl + 2 * W}); lower "
                      "batch_size or load a larger chunk")
        Ml = (max(Bl, int(round(Bl * cfg.oversample)))
              if cfg.oversample > 1 else Bl)
        return min(Ml, n - 2 * W) * dp

    def _build_corpus_step(self, n_steps: int, M: int):
        """Fused sample+train over a device-resident corpus chunk.

        The host pipeline ships every batch over PCIe/DCN; here the corpus
        ids live in HBM and each scan iteration *samples* a batch on device
        (positions, window offset with the reference's random shrink,
        subsampling keep-test) and trains it — ``n_steps`` batches per
        dispatch with no per-batch host traffic. This is the TPU-native form
        of the reference's loader-thread + pipelined-trainer overlap
        (``distributed_wordembedding.cpp:199-208``).

        With ``dp_sync="dispatch"`` and worker axis > 1 the whole dispatch
        runs under shard_map with the worker axis manual: each worker
        samples its ``M // dp`` candidate slab from its own arc of the
        cyclic chunk (the in-mesh form of the per-process data partition),
        trains against a local table copy, and the dispatch ends with ONE
        summed-delta psum — no per-batch table collectives (the dense
        grad-table allreduce the reference never pays either; its sync
        Adds are sparse row buckets, ``src/table/matrix_table.cpp:288-316``).
        """
        cfg = self.config
        W, B = cfg.window, cfg.batch_size
        step = self._raw_step
        dp = self._dp_local()
        S = n_steps
        # per-worker candidate slab / batch (dp == 1: the global sizes)
        Ml, Bl = M // dp, B // dp
        G = max(int(cfg.shared_negatives), 1)
        draws_per_call = S * (Bl // G) * cfg.negative
        neg_pool = (self._ensure_neg_pool(draws_per_call)
                    if cfg.negative > 0 and cfg.neg_pool_size > 0 else None)

        def fused(w_in, w_out, g_in, g_out, ext_ids, ext_sents, ext_disc,
                  lr, key, start0):
            """Sequential corpus streaming (the reference reads sentences in
            order — ``WE/src/reader.cpp``): each step consumes the next Ml
            corpus positions as centers, so every word lookup is a contiguous
            slice instead of a scalar gather. The per-pair window offset is
            resolved by selecting among the 2W statically-shifted copies of
            the slab — pure vector ops, no gathers. The wrap-around-extended
            buffers are precomputed once per chunk (``load_corpus_chunk``).
            """
            n = ext_ids.shape[0] - M - 2 * W

            saved = (w_in, w_out, g_in, g_out)
            if dp > 1:
                key, key_out, (w_in, w_out, g_in, g_out) = _dp_enter(
                    key, saved)
                # each worker streams its own arc of the cyclic chunk
                widx = jax.lax.axis_index(WORKER_AXIS)
                start0 = (start0 + widx * (n // dp)) % n

            # ---- bulk RNG: ONE vectorized draw for all S batches ----
            key, k1, k2, k3, k4, k5 = jax.random.split(key, 6)
            shrink = jax.random.randint(k1, (S, Ml), 1, W + 1)
            if not cfg.cbow:
                dmag = jnp.minimum(jax.random.randint(k2, (S, Ml), 1, W + 1),
                                   shrink)
                sign = jnp.where(jax.random.bernoulli(k3, 0.5, (S, Ml)), 1, -1)
                # window offset -W..W (excl 0) → shifted-copy index 0..2W-1
                dsel = jnp.where(sign > 0, W + dmag - 1, W - dmag)
                u_ctx = jax.random.uniform(k5, (S, Ml))
            else:
                dsel = None
                u_ctx = jax.random.uniform(k5, (S, Ml, 2 * W))
            u_center = jax.random.uniform(k4, (S, Ml))
            negs = None
            if cfg.negative > 0:
                key, kn = jax.random.split(key)
                n_rows = Bl // G
                if neg_pool is not None:
                    negs = pool_negatives(kn, neg_pool,
                                          (S, n_rows, cfg.negative))
                else:
                    negs = sample_negatives(kn, self._packed_alias,
                                            (S, n_rows, cfg.negative))

            starts = (start0 + jnp.arange(S, dtype=jnp.int32) * Ml) % n

            offsets = np.concatenate([np.arange(-W, 0), np.arange(1, W + 1)])

            def slab_views(start):
                """[2W+1 views of the slab] — static slices of one dynamic
                slice, so the only data movement is contiguous."""
                buf = jax.lax.dynamic_slice(ext_ids, (start,), (Ml + 2 * W,))
                sbuf = jax.lax.dynamic_slice(ext_sents, (start,),
                                             (Ml + 2 * W,))
                dbuf = jax.lax.dynamic_slice(ext_disc, (start,),
                                             (Ml + 2 * W,))
                ctr = (buf[W:W + Ml], sbuf[W:W + Ml], dbuf[W:W + Ml])
                shifted = [(buf[W + d:W + d + Ml], sbuf[W + d:W + d + Ml],
                            dbuf[W + d:W + d + Ml]) for d in offsets]
                return ctr, shifted

            def select(shifted_vals, dsel_row):
                """contexts[i] = shifted[dsel[i]][i] via masked sum (2W
                vector multiply-adds, no gather)."""
                out = jnp.zeros_like(shifted_vals[0])
                for j, v in enumerate(shifted_vals):
                    out = jnp.where(dsel_row == j, v, out)
                return out

            def sample_sg(start, dsel, u_center, u_ctx):
                (centers, csent, cdisc), shifted = slab_views(start)
                contexts = select([s[0] for s in shifted], dsel)
                xsent = select([s[1] for s in shifted], dsel)
                xdisc = select([s[2] for s in shifted], dsel)
                valid = (xsent == csent)
                keep = (u_center >= cdisc) & (u_ctx >= xdisc)
                ok = valid & keep
                if Ml > Bl:
                    centers, contexts, ok = pack_survivors(
                        ok, Bl, centers, contexts)
                return centers, contexts, ok.astype(jnp.float32)

            def sample_cbow(start, shrink, u_center, u_ctx):
                (centers, csent, cdisc), shifted = slab_views(start)
                contexts = jnp.stack([s[0] for s in shifted], axis=1)
                xsent = jnp.stack([s[1] for s in shifted], axis=1)
                xdisc = jnp.stack([s[2] for s in shifted], axis=1)
                in_window = (jnp.abs(offsets)[None, :]
                             <= shrink[:, None])              # [M, 2W]
                valid = in_window & (xsent == csent[:, None])
                keep = (u_center >= cdisc)[:, None] & (u_ctx >= xdisc)
                ok = valid & keep
                if Ml > Bl:
                    centers, contexts, ok, ex_packed = pack_survivors(
                        ok.any(axis=1), Bl, centers, contexts, ok)
                    ok = ok & ex_packed[:, None]
                return centers, contexts, ok.astype(jnp.float32)

            def body(carry, xs):
                w_in, w_out, g_in, g_out, key = carry
                if cfg.cbow:
                    start, shrink_r, u_c, u_x, nn = xs
                    c, t, m = sample_cbow(start, shrink_r, u_c, u_x)
                    count = (m.sum(axis=1) > 0).astype(jnp.float32).sum()
                else:
                    start, dsel_r, u_c, u_x, nn = xs
                    c, t, m = sample_sg(start, dsel_r, u_c, u_x)
                    count = m.sum()
                w_in, w_out, g_in, g_out, loss, key = step(
                    w_in, w_out, g_in, g_out, c, t, m, lr, key, nn)
                return (w_in, w_out, g_in, g_out, key), (loss, count)

            dummy_negs = (negs if negs is not None
                          else jnp.zeros((S, 1), jnp.int32))
            if cfg.cbow:
                xs = (starts, shrink, u_center, u_ctx, dummy_negs)
            else:
                xs = (starts, dsel, u_center, u_ctx, dummy_negs)

            def body_wrap(carry, xs):
                if cfg.negative <= 0:
                    xs = xs[:-1] + (None,)
                return body(carry, xs)

            (w_in, w_out, g_in, g_out, key), (losses, counts) = jax.lax.scan(
                body_wrap, (w_in, w_out, g_in, g_out, key), xs)
            loss, count = losses.mean(), counts.sum()
            if dp > 1:
                w_in, w_out, g_in, g_out = _dp_exchange(
                    (w_in, w_out, g_in, g_out), saved,
                    mode=cfg.dp_exchange, cap=self._keyed_cap())
                loss = jax.lax.psum(loss, WORKER_AXIS) / dp
                count = jax.lax.psum(count, WORKER_AXIS)
                key = key_out
            return (w_in, w_out, g_in, g_out, loss, count, key)

        if dp > 1:
            from jax.sharding import PartitionSpec as P

            fused = jax.shard_map(
                fused, mesh=self.input_table.mesh,
                in_specs=(P(),) * 10, out_specs=(P(),) * 7,
                axis_names={WORKER_AXIS})
        return jax.jit(
            fused,
            donate_argnums=(0, 1, 2, 3),
            in_shardings=self._state_shardings
            + (None, None, None, None, self._key_sharding, None),
            out_shardings=self._state_shardings
            + (None, None, self._key_sharding),
        )

    def _dispatch(self, step_fn, centers, contexts, mask, n_words: int):
        cfg = self.config
        lr = jnp.float32(self.current_lr())
        g_in = self._g_in if cfg.use_adagrad else None
        g_out = self._g_out if cfg.use_adagrad else None
        batch = (jnp.asarray(centers, jnp.int32),
                 jnp.asarray(contexts, jnp.int32),
                 jnp.asarray(mask, jnp.float32))
        if jax.process_count() > 1 and len(
                self.input_table.mesh.devices.flat) > len(
                jax.local_devices()):
            # multi-process SPMD (the worker axis spans processes): each
            # process passes ITS batch shard; assemble the global array
            # from the per-process local data (a plain device_put cannot
            # target non-addressable shards). Global batch = local x P.
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self.input_table.mesh
            # batch dim is axis 0 for single batches, axis 1 for stacked
            # [S, B] multi-batch calls; trailing dims (CBOW window) unsharded
            lead = (None,) if batch[0].ndim >= 2 else ()
            batch = tuple(
                jax.make_array_from_process_local_data(
                    NamedSharding(
                        mesh, P(*(lead + (WORKER_AXIS,))[:a.ndim])),
                    np.asarray(a))
                for a in batch)
        with self.input_table._lock, self.output_table._lock:
            (self.input_table._data, self.output_table._data,
             g_in, g_out, loss, self._key) = step_fn(
                self.input_table._data, self.output_table._data,
                g_in, g_out, *batch, lr, self._key)
            self.input_table.version += 1
            self.output_table.version += 1
        if cfg.use_adagrad:
            self._g_in, self._g_out = g_in, g_out
        self._words_trained += n_words
        return loss

    def _batch_words(self, mask: np.ndarray) -> float:
        """Word-unit progress for a batch (see ``current_lr``)."""
        if self.config.cbow:
            # one CBOW example == one center-word occurrence
            return float((mask.sum(axis=-1) > 0).sum())
        return self._pairs_to_words(float(mask.sum()))

    def train_batch(self, centers: np.ndarray, contexts: np.ndarray,
                    mask: Optional[np.ndarray] = None) -> float:
        """Train one fixed-size batch.

        Skip-gram: ``centers [B]``, ``contexts [B]``, ``mask [B]``.
        CBOW: ``centers [B]``, ``contexts [B, 2*window]``, ``mask [B, 2W]``
        (per-context-slot validity). Returns the mean loss (async jax
        scalar; float() to block).
        """
        if mask is None:
            mask = np.ones(contexts.shape, np.float32)
        return self._dispatch(self._step, centers, contexts, mask,
                              self._batch_words(np.asarray(mask)))

    def train_batches(self, centers: np.ndarray, contexts: np.ndarray,
                      mask: Optional[np.ndarray] = None) -> float:
        """Train a stack of batches [S, B(, C)] in ONE device dispatch."""
        if mask is None:
            mask = np.ones(contexts.shape, np.float32)
        return self._dispatch(self._multi_step, centers, contexts, mask,
                              self._batch_words(np.asarray(mask)))

    # -- device-resident corpus path (the fast path) -----------------------
    def load_corpus_chunk(self, ids: np.ndarray, sent_ids: np.ndarray,
                          discard: Optional[np.ndarray] = None) -> None:
        """Upload a corpus chunk to HBM (ids + sentence membership + word
        discard probabilities for subsampling)."""
        self._corpus = jnp.asarray(ids, jnp.int32)
        self._sents = jnp.asarray(sent_ids, jnp.int32)
        if discard is None:
            discard = np.zeros(self.config.vocab_size, np.float32)
        self._discard = jnp.asarray(discard, jnp.float32)
        # Hoist the wrap-around extension + per-position discard gather out
        # of the fused step: they are O(corpus) and depend only on the chunk
        # (profiled at ~13 ms/dispatch on a 2M-token chunk — pure waste when
        # re-done every call).
        n = int(self._corpus.shape[0])
        M = self._candidate_batch(n)
        W = self.config.window

        def _ext(corpus, sents, discard):
            dpos = jnp.take(discard, corpus, axis=0)
            return (
                jnp.concatenate([corpus[-W:], corpus, corpus[:M + W]]),
                jnp.concatenate([sents[-W:], sents, sents[:M + W]]),
                jnp.concatenate([dpos[-W:], dpos, dpos[:M + W]]),
            )

        self._ext_bufs = jax.jit(_ext)(self._corpus, self._sents,
                                       self._discard)
        if self.config.row_mean_updates and self.config.row_mean_static:
            self._build_static_scales(np.asarray(discard, np.float64))
        # the originals are folded into the ext buffers; keeping them would
        # pin a second copy of the corpus in HBM for the model's lifetime
        self._corpus_len = n
        del self._corpus, self._sents, self._discard

    def _build_static_scales(self, discard: np.ndarray) -> None:
        """Expected-count scale tables (``row_mean_static``): per step,
        row v's expected colliding grads are

        * input table (sg centers / cbow context slots):
          ``B * p_eff(v)`` (x expected window slots for cbow),
        * output table: ``B * p_eff(v) + B * K * p_neg(v)``
          (targets + negatives),

        where ``p_eff`` is the subsampled unigram law and ``p_neg`` the
        unigram^0.75 law — the same distributions the device sampler
        draws from. Scale = min(E, cap)/max(E, 1), the expectation form
        of ``_row_scale_vec``. The tables change only with the discard
        vector; chunk rotation reuses them (same corpus law), and a new
        law invalidates the fused cache.
        """
        cfg = self.config
        counts = np.asarray(self._host_counts, np.float64)
        keep = np.clip(1.0 - discard, 0.0, 1.0)
        eff = counts * keep
        p_eff = eff / max(eff.sum(), 1e-12)
        w75 = counts ** 0.75
        p_neg = w75 / max(w75.sum(), 1e-12)
        # the table application unit is the PER-WORKER batch: with
        # dp_sync="dispatch" each worker applies its own Bl-sized batches
        # locally, so the expected colliding grads per application scale
        # with Bl, not the global batch
        B, K = cfg.batch_size // self._dp_local(), cfg.negative
        e_in = B * p_eff                      # sg centers (sg-only mode)
        e_out = B * p_eff + B * K * p_neg     # targets + negatives

        def scale(e):
            c = np.maximum(e, 1.0)
            s = np.minimum(c, max(float(cfg.row_update_cap), 1.0)) / c
            return jnp.asarray(s, jnp.float32)

        new_in, new_out = scale(e_in), scale(e_out)
        if (self._static_scale_in is not None
                and not (np.allclose(np.asarray(self._static_scale_in),
                                     np.asarray(new_in))
                         and np.allclose(np.asarray(self._static_scale_out),
                                         np.asarray(new_out)))):
            # every traced program captured the old tables as constants:
            # drop the fused cache AND rebuild the batch-step jits
            self._fused_cache = {}
            self._static_scale_in, self._static_scale_out = new_in, new_out
            self._step = self._build_step()
            return
        self._static_scale_in, self._static_scale_out = new_in, new_out

    def train_device_steps(self, n_steps: int) -> Tuple[Any, Any]:
        """Run ``n_steps`` sample+train iterations on device in one dispatch.

        Returns (mean_loss, pairs_trained) as async jax scalars.
        """
        if not hasattr(self, "_ext_bufs"):
            Log.fatal("call load_corpus_chunk() before train_device_steps()")
        n = self._corpus_len
        M = self._candidate_batch(n)
        fused = getattr(self, "_fused_cache", {}).get((n_steps, M))
        if fused is None:
            if not hasattr(self, "_fused_cache"):
                self._fused_cache = {}
            fused = self._build_corpus_step(n_steps, M)
            self._fused_cache[(n_steps, M)] = fused
        cfg = self.config
        lr = jnp.float32(self.current_lr())
        g_in = self._g_in if cfg.use_adagrad else None
        g_out = self._g_out if cfg.use_adagrad else None
        start0 = self._stream_pos % n
        # the cursor is a PER-WORKER arc position: each of the dp workers
        # consumes n_steps * (M // dp) positions of its own arc per
        # dispatch (the in-jit widx*(n//dp) offsets place the arcs), so
        # advancing by the global M would skip/alias corpus coverage
        self._stream_pos = (start0 + n_steps * (M // self._dp_local())) % n
        # read-and-rebind of table state stays under BOTH table locks so a
        # concurrent async-PS drain apply can never land between the read
        # and the rebind (it would be silently overwritten)
        with self.input_table._lock, self.output_table._lock:
            (self.input_table._data, self.output_table._data,
             g_in, g_out, loss, count, self._key) = fused(
                self.input_table._data, self.output_table._data,
                g_in, g_out, *self._ext_bufs,
                lr, self._key, jnp.int32(start0))
            self.input_table.version += 1
            self.output_table.version += 1
        if cfg.use_adagrad:
            self._g_in, self._g_out = g_in, g_out
        # lr decay bookkeeping: count is async; approximate with the
        # expected valid fraction to avoid a sync point (word units).
        est_examples = n_steps * cfg.batch_size * 0.5
        self._words_trained += (est_examples if cfg.cbow
                                else self._pairs_to_words(est_examples))
        return loss, count


@dataclass
class HuffmanCodes:
    """Padded Huffman paths for HS (reference HuffmanEncoder output)."""

    paths: np.ndarray  # [vocab, L] inner-node ids
    codes: np.ndarray  # [vocab, L] bits (float)
    mask: np.ndarray   # [vocab, L] valid-step mask


def build_huffman(counts: np.ndarray, max_code_length: int = 40) -> HuffmanCodes:
    """Build Huffman tree over word counts (reference ``HuffmanEncoder``,
    ``WE/src/huffman_encoder.cpp``); returns padded per-word paths."""
    import heapq

    n = counts.shape[0]
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    parent = {}
    binary = {}
    next_id = n
    while len(heap) > 1:
        c1, i1 = heapq.heappop(heap)
        c2, i2 = heapq.heappop(heap)
        parent[i1], parent[i2] = next_id, next_id
        binary[i1], binary[i2] = 0, 1
        heapq.heappush(heap, (c1 + c2, next_id))
        next_id += 1
    root = heap[0][1] if heap else None
    L = max_code_length
    paths = np.zeros((n, L), np.int32)
    codes = np.zeros((n, L), np.float32)
    mask = np.zeros((n, L), np.float32)
    for w in range(n):
        path, bits = [], []
        node = w
        while node in parent:
            bits.append(binary[node])
            node = parent[node]
            path.append(node)
        # path root->leaf; inner node ids are offset into [0, n-1) range
        path = path[::-1][:L]
        bits = bits[::-1][:L]
        for j, (p, b) in enumerate(zip(path, bits)):
            paths[w, j] = p - n  # inner nodes numbered n..2n-2 -> 0..n-2
            codes[w, j] = b
            mask[w, j] = 1.0
    return HuffmanCodes(paths=paths, codes=codes, mask=mask)
