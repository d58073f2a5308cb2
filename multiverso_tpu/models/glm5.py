"""A ``glm_moe_dsa`` language model on the serving path (first served:
GLM-5), one chip's share: DeepSeek-V3's layer pattern with DeepSeek-V3.2's
learned sparse attention (DSA) in every layer.

Layer ``l`` is ``models/deepseek_v3.py``'s (a dense SwiGLU FFN in the
``first_k_dense_replace`` leading layers, the sigmoid-routed experts
and a shared expert after; the router with ``n_group`` 1 is a plain
top-k of score plus bias), with one change in its attention: each
query reads only the ``index_topk`` cached positions that an INDEXER
ranks highest. With ``x`` the layer's normed input and ``c_q = N(x
W_qa)`` MLA's query latent (``longcat.query_latent``):

* index queries ``q^I = c_q W_Iq``, ``index_n_heads`` of
  ``index_head_dim``; an index key a token ``k^I = LayerNorm(x W_Ik)``;
  the first ``qk_rope_head_dim`` dimensions of both rotated (DeepSeek's
  interleaved pairs, MLA's frequencies); head weights ``w = (x W_Iw)
  Hi^-1/2 dI^-1/2``;
* the score of position ``s`` for query ``t``: ``I(t, s) = sum_j w_j
  relu(q^I_j(t) . k^I(s))`` in float32 (:func:`ops.sparse_attention.
  index_scores`), over ``s <= t``;
* ``S(t)``: the ``min(index_topk, t + 1)`` positions of largest ``I``;
  MLA's softmax (``longcat.py``'s, scores over ``sqrt(dn + dr)``) runs
  over ``S(t)`` alone.

**Where the index keys live.** In pool 0, the latent pool, under the
same block table as the latent rows: the pool has ``num_hidden_layers``
sublayers of latent rows and then :attr:`GLM5Config.index_rows` more,
whose rows pack the layers' index keys side by side (layer ``l``'s at
lanes ``l dI`` on, :func:`index_location`; at the published sizes five
128-wide keys fill one 640-wide row). So a block carries everything a
token leaves behind: copy-on-write copies the keys with the latents,
and a prefix hit or a release treats them alike; nothing in the engine
knows of them.

**The two programs.** A decode step writes its latent row and index
key, scores every live position of its slot (on a TPU the Pallas kernel
:func:`ops.sparse_attention.paged_index_scores` reads the keys in place;
elsewhere over the gathered keys), takes ``jax.lax.top_k``, gathers the
selected latent rows by (block table, offset) and attends in the latent
over them alone (:func:`ops.sparse_attention.sparse_latent_attention`).
A prefill chunk scores its queries against the slot's keys and ANDs the
exact top-k mask (:func:`ops.sparse_attention.top_k_mask`) into the
expanded attention's causal mask (``longcat.chunk_attend``'s
``select``), as DeepSeek's own prefill runs DSA as masked dense
attention, over the key tiles up to the chunk's last valid row
(``longcat.mla_expanded_tiled``). :meth:`GLM5LM.attended_positions`
runs the two functions once more over one sequence and hands out the
positions a step's attention read in each layer, so that a check can
hold the selection itself, and not only the tokens it yields, to a
reference.

Departures from the published inference code: the indexer runs on
bfloat16 operands with float32 accumulation where that code casts to
FP8 after a Hadamard rotation; the rotation is orthonormal, so it
changes no score in exact arithmetic, and is left out. Not here: the
multi-token-prediction module (``num_nextn_predict_layers``; a drafter
needs a verify step over the latent pool, which
``longcat.latent_pool_programs`` refuses by name). Weights are drawn ON
THE DEVICE leaf by leaf from one law (:func:`init_params`); the model is
serve-only and its snapshot is the weights themselves.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops import sparse_attention as dsa
from ..ops.moe import COUNT_SCALARS
from ..ops.sparse_attention import index_scores, top_k_mask
from . import deepseek_v3, longcat
from .longcat import rope, rope_frequencies

# columns of the counters' last row (the attention's own): over the
# decode steps, positions the indexer scored and positions attended,
# summed over layers; step runs; and over the prefill chunks, chunk runs
# and the prompt tokens they held
(_SCORED, _ATTENDED, _STEP_RUNS, _CHUNK_RUNS,
 _CHUNK_TOKENS) = range(5)
# the key LayerNorm's epsilon (DeepSeek-V3.2's indexer)
_KEY_NORM_EPS = 1e-6
# block rows of the pool that ``GLM5LM.logits`` runs its one chunk in
_FORWARD_BLOCK = 16


@dataclasses.dataclass(frozen=True)
class GLM5Config(deepseek_v3.DeepSeekV3Config):
    vocab_size: int = 154880           # rows held here (a slice)
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    qk_nope_head_dim: int = 192
    v_head_dim: int = 256
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048

    @property
    def index_rows(self) -> int:
        """Pool sublayers that hold the index keys, every layer's side by
        side."""
        return -(-self.num_hidden_layers * self.index_head_dim
                 // self.pool_width)

    @property
    def n_sublayers(self) -> int:
        """Pool 0's depth: a latent row a layer, then the index rows."""
        return self.num_hidden_layers + self.index_rows


def config_from_dict(cfg: dict, seed: int) -> GLM5Config:
    """From a configuration file's dict (the published key names; the
    rotary's ``rope_parameters`` group read into ``rope_theta``). Refused
    by name: a rotary type other than ``default``, rotary pairs that are
    not interleaved, a ``qk_head_dim`` that is not ``nope + rope``."""
    rope_p = cfg.get("rope_parameters", {})
    if rope_p.get("rope_type", "default") != "default":
        Log.fatal(f"GLM5LM: rope_type {rope_p['rope_type']!r} is not "
                  "supported: the rotary is plain")
    for key in ("rope_interleave", "indexer_rope_interleave"):
        if not cfg.get(key, True):
            Log.fatal(f"GLM5LM: {key} false is not supported: rotary pairs "
                      "are interleaved")
    if cfg.get("qk_head_dim", cfg["qk_nope_head_dim"]
               + cfg["qk_rope_head_dim"]) != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"]:
        Log.fatal(f"GLM5LM: qk_head_dim {cfg['qk_head_dim']} is not "
                  "qk_nope_head_dim + qk_rope_head_dim")
    flat = dict(cfg, rope_scaling=None)
    if "rope_theta" in rope_p:
        flat["rope_theta"] = rope_p["rope_theta"]
    return longcat.share_config(GLM5Config, flat, seed)


# -- the weight law -----------------------------------------------------------
# As deepseek_v3.py's: a key per (layer, leaf) from the seed, and per routed
# expert by its router output index; float32 normal on the device times the
# leaf's std, rounded to the configuration's dtype; router, bias and the
# key norm's gain and bias stay float32. The reference keeps its own copy.
# std is 1/sqrt(fan_in) but: W_qb 1.5 times it (queries of 1.5 an element
# against unit keys over 256 dimensions, over sqrt(256): attention scores
# of std near 1.5, neither flat nor one-hot); W_o 4 times it (as
# deepseek_v3.py's: attention then moves the stream as an FFN does); the
# router's bias 0.02 an output (it moves picks). The key LayerNorm's gain
# is 1 + 0.5 z and its bias 0.5 z, z normal: at its initial 1 and 0 a
# normed key differs from the raw projection by a shift and scale of a
# few percent a key, and a fault that drops the norm would hardly move
# the selection
_LEAVES = deepseek_v3._LEAVES + ("w_iq", "w_ik", "ik_gain", "ik_bias",
                                 "w_iw")
_QB_GAIN = 1.5
_WO_GAIN = 4.0
_KEY_NORM_SPREAD = 0.5


def _leaf_key(seed: int, layer: int, leaf: str):
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
                             layer + 1)
    return jax.random.fold_in(key, _LEAVES.index(leaf))


def draw_indexer(cfg: GLM5Config, draw, key) -> Dict[str, Any]:
    """One layer's indexer leaves, ``key(leaf)`` the leaf's key."""
    D, rq, Hi, dI = cfg.hidden_size, cfg.q_lora_rank, cfg.index_n_heads, \
        cfg.index_head_dim
    f32, dt = jnp.float32, cfg.dtype
    return {"w_iq": draw(key("w_iq"), (rq, Hi * dI), rq ** -0.5, dt),
            "w_ik": draw(key("w_ik"), (D, dI), D ** -0.5, dt),
            "ik_gain": 1.0 + draw(key("ik_gain"), (dI,), _KEY_NORM_SPREAD,
                                  f32),
            "ik_bias": draw(key("ik_bias"), (dI,), _KEY_NORM_SPREAD, f32),
            "w_iw": draw(key("w_iw"), (D, Hi), D ** -0.5, dt)}


def init_params(cfg: GLM5Config) -> Dict[str, Any]:
    """The share's weights, each leaf one jitted draw on the default
    device (never the whole tree at once, never on the host). A layer's
    indexer lies beside its MLA leaves (``layers[l]["mla"]``)."""
    D = cfg.hidden_size
    draws = deepseek_v3.drawers(cfg.dtype)
    draw = draws[0]
    layers = []
    for l in range(cfg.num_hidden_layers):
        k = lambda leaf, l=l: _leaf_key(cfg.seed, l, leaf)
        mla = longcat.draw_mla(cfg, draw, k, _QB_GAIN, _WO_GAIN)
        mla.update(draw_indexer(cfg, draw, k))
        layers.append({"mla": mla, "ffn_norm": jnp.ones((D,), jnp.float32),
                       **deepseek_v3.draw_ffn_layer(cfg, draws, k, l)})
    return {"embed": draw(_leaf_key(cfg.seed, -1, "embed"),
                          (cfg.vocab_size, D), 1.0, cfg.dtype),
            "head": draw(_leaf_key(cfg.seed, -1, "head"),
                         (D, cfg.vocab_size), D ** -0.5, cfg.dtype),
            "final_norm": jnp.ones((D,), jnp.float32),
            "layers": layers}


# -- the indexer --------------------------------------------------------------
def layer_norm(x, gain, bias, eps: float):
    """LayerNorm over the last axis, float32."""
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def _rotate_head(cfg, x, pos):
    """The first ``qk_rope_head_dim`` values of each ``x`` [T, ..., dI]
    rotated, the rest as they are."""
    dr = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [rope(x[..., :dr], pos, rope_frequencies(cfg)), x[..., dr:]], -1)


def indexer_project(cfg: GLM5Config, w, x, c_q, pos):
    """The indexer of the normed ``x`` [T, D] at positions ``pos``, from
    MLA's query latent ``c_q`` [T, rq]: ``(q [T, Hi, dI], k [T, dI]`` in
    the model's dtype, ``weights [T, Hi]`` float32)."""
    f32, dt = jnp.float32, cfg.dtype
    Hi, dI = cfg.index_n_heads, cfg.index_head_dim
    T = x.shape[0]
    q = jnp.dot(c_q, w["w_iq"], preferred_element_type=f32).reshape(T, Hi,
                                                                   dI)
    k = layer_norm(jnp.dot(x, w["w_ik"], preferred_element_type=f32),
                   w["ik_gain"], w["ik_bias"], _KEY_NORM_EPS)
    weights = jnp.dot(x, w["w_iw"], preferred_element_type=f32) \
        * (Hi ** -0.5 * dI ** -0.5)
    return (_rotate_head(cfg, q, pos).astype(dt),
            _rotate_head(cfg, k, pos).astype(dt), weights)


def index_location(cfg: GLM5Config, l: int) -> Tuple[int, int]:
    """``(pool sublayer, first lane)`` of layer ``l``'s index keys."""
    at = l * cfg.index_head_dim
    return cfg.num_hidden_layers + at // cfg.pool_width, at % cfg.pool_width


def write_index_keys(cfg: GLM5Config, pool, l: int, keys, block, offset):
    """Layer ``l``'s index keys [n, dI] written at ``(block, offset)``
    [n] of the pool. The write is a read-modify-write of whole rows:
    the rows are gathered, the layer's lanes set, and the rows scattered
    back, which XLA:TPU lowers as one scatter; a scatter of the lane
    window alone it expands into a loop of one-row updates of the whole
    pool. Other layers' lanes are read back and kept; rows that coincide
    (pad rows and dead slots, all in scratch block 0) are never read."""
    sub, lane = index_location(cfg, l)
    rows = pool[sub, block, offset]
    rows = jax.lax.dynamic_update_slice_in_dim(rows, keys, lane, axis=1)
    return pool.at[sub, block, offset].set(rows)


def index_keys(cfg: GLM5Config, pool, l: int, tables, t: int):
    """Layer ``l``'s index keys of the blocks ``tables`` ([S, M] or [M])
    names, cut to ``t`` positions: [..., t, dI]."""
    sub, lane = index_location(cfg, l)
    return longcat._view(pool, sub, tables, t)[
        ..., lane:lane + cfg.index_head_dim]


# -- paged programs -----------------------------------------------------------
def decode_step_paged(cfg: GLM5Config, params, pool, counters, block_tables,
                      tok, pos, active, t_logical: int, paged_attention=None,
                      index_kernel=None, with_selection: bool = False):
    """One fused token step over S slots against the paged latent pool
    (``longcat.decode_step_paged``'s contract). In each layer a slot
    writes its latent row and index key, its indexer scores the slot's
    live positions (``index_kernel``,
    :func:`ops.sparse_attention.paged_index_scores` where
    :meth:`GLM5LM.serving_programs` finds it applies, reads the keys in
    place; else the keys are gathered), the ``index_topk`` best are
    gathered and attended in the latent. ``paged_attention``, the dense
    kernel every latent model's step is handed, has no part here: a step
    reads the selected rows only. The counters count the positions
    scored and those the attention read off the scores and the mask it
    attends under. Returns ``(pool, counters, next_tok, pos)``, and with
    ``with_selection`` (:meth:`GLM5LM.attended_positions`) last the
    positions each layer's attention read, ``[L, S, K]`` int32, -1 in
    a column it did not."""
    del paged_attention
    f32 = jnp.float32
    n_sub, N, Bs, W = pool.shape
    write_blk, write_off, lengths = longcat.step_rows(pool, block_tables,
                                                      pos, active)
    K = min(cfg.index_topk, t_logical)
    live_pos = jnp.arange(t_logical)[None, :] < lengths[:, None]
    h = jnp.take(params["embed"], tok, axis=0).astype(f32)
    # a layer each: (positions scored, positions its attention read)
    picked = []

    def attend(w, x, pool, l):
        c_q = longcat.query_latent(cfg, w, x)
        q_nope, q_rope, row = longcat.mla_project(cfg, w, x, pos, W, c_q)
        q_i, k_i, w_i = indexer_project(cfg, w, x, c_q, pos)
        pool = pool.at[l, write_blk, write_off].set(row)
        pool = write_index_keys(cfg, pool, l, k_i, write_blk, write_off)
        if index_kernel is not None:
            sub, lane = index_location(cfg, l)
            scores = index_kernel(q_i, w_i, pool.reshape(n_sub * N, Bs, W),
                                  sub * N + block_tables, lengths,
                                  lane=lane)[:, :t_logical]
        else:
            keys = index_keys(cfg, pool, l, block_tables, t_logical)
            scores = jax.vmap(lambda q, wt, k: index_scores(
                q[None], wt[None], k)[0])(q_i, w_i, keys)
            scores = jnp.where(live_pos, scores, -jnp.inf)
        top, chosen = jax.lax.top_k(scores, K)
        read = top > -jnp.inf
        picked.append((jnp.sum(scores > -jnp.inf), jnp.where(read, chosen,
                                                             -1)))
        rows = dsa.gather_rows(pool, l, block_tables, chosen)
        o_lat = dsa.sparse_latent_attention(
            longcat.latent_query(cfg, w, q_nope, q_rope, W), rows, read,
            scale=1.0 / cfg.softmax_divisor, wv=cfg.kv_lora_rank)
        return pool, longcat.latent_output(cfg, w, o_lat)

    h, pool, counters = deepseek_v3._walk(cfg, params, h, pool, counters,
                                          attend, active)
    attended = jnp.stack([a for _, a in picked])
    counters = counters.at[-1, _SCORED:_STEP_RUNS + 1].add(jnp.stack([
        sum(n for n, _ in picked).astype(f32),
        jnp.sum(attended >= 0).astype(f32), jnp.ones((), f32)]))
    out = (pool, counters) + longcat.greedy_next(cfg, params, h, tok, pos,
                                                 active)
    return out + (attended,) if with_selection else out


def _chunk_walk(cfg: GLM5Config, params, pool, counters, rows, tokens,
                t_logical: int):
    """Every layer over one chunk's ``tokens`` at ``rows``
    (``longcat.chunk_rows``): in each, the chunk writes its index keys,
    scores its queries against the slot's keys up to each query's
    position, and ANDs each query's exact ``index_topk`` best
    (:func:`ops.sparse_attention.top_k_mask`) into the expanded
    attention's causal mask. Returns ``(h, pool, counters)``."""
    bt_row, pos_ix, valid, blk_ix, off, causal = rows
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def attend(w, x, pool, l):
        def select(pool, c_q):
            q_i, k_i, w_i = indexer_project(cfg, w, x, c_q, pos_ix)
            pool = write_index_keys(cfg, pool, l, k_i, blk_ix, off)
            scores = index_scores(q_i, w_i, index_keys(cfg, pool, l, bt_row,
                                                       t_logical))
            return pool, top_k_mask(jnp.where(causal, scores, -jnp.inf),
                                    cfg.index_topk)

        return longcat.chunk_attend(cfg, w, x, pool, l, rows, t_logical,
                                    select)

    return deepseek_v3._walk(cfg, params, h, pool, counters, attend, valid)


def prefill_chunk_paged(cfg: GLM5Config, params, pool, counters,
                        block_tables, slot, tokens, offset, length,
                        t_logical: int):
    """Incremental prefill of one fixed-size chunk of ONE slot
    (``longcat.prefill_chunk_paged``'s contract; the layers as
    :func:`_chunk_walk` has them). Returns ``(pool, counters,
    last_logits [V])``."""
    rows = longcat.chunk_rows(pool, block_tables, slot, tokens.shape[0],
                              offset, length, t_logical)
    h, pool, counters = _chunk_walk(cfg, params, pool, counters, rows,
                                    tokens, t_logical)
    counters = counters.at[-1, _CHUNK_RUNS:_CHUNK_TOKENS + 1].add(
        jnp.stack([jnp.ones((), jnp.float32),
                   jnp.sum(rows[2].astype(jnp.float32))]))
    return pool, counters, longcat.last_logits(cfg, params, h, length)


def counters_summary(cfg: GLM5Config, counts: np.ndarray) -> dict:
    """``eng.stats()``'s keys from the accumulated counters: the expert
    layers' rows as ``deepseek_v3.routing_summary`` reads them, and from
    the last row, over the decode steps, ``dsa_scored_positions`` (live
    positions the indexer scored, summed over layers),
    ``dsa_attended_positions`` (those the attention read, counted off
    the mask it attends under: ``min(length, index_topk)`` a live slot
    and layer where the selection is sound), their ratio
    ``dsa_selected_share`` (1.0: the selection never bit) and
    ``dsa_step_runs``; over the prefill chunks ``dsa_chunk_runs`` and
    ``dsa_chunk_tokens`` (the prompt tokens they held). Runs are counted
    beside the positions so that a reader can take a mean a run and
    multiply it by the runs a trace holds."""
    c = np.asarray(counts, np.float64)
    out = deepseek_v3.routing_summary(cfg, c[:-1])
    own = c[-1]
    out.update(dsa_scored_positions=float(own[_SCORED]),
               dsa_attended_positions=float(own[_ATTENDED]),
               dsa_step_runs=float(own[_STEP_RUNS]),
               dsa_chunk_runs=float(own[_CHUNK_RUNS]),
               dsa_chunk_tokens=float(own[_CHUNK_TOKENS]))
    if own[_SCORED] > 0:
        out["dsa_selected_share"] = float(own[_ATTENDED] / own[_SCORED])
    return out


class GLM5LM:
    """Serve-only ``glm_moe_dsa`` share: weights drawn on the device from
    ``config.seed``; the snapshot contract and the engine's seam as
    :class:`longcat.LongCatLM` has them."""

    def __init__(self, config: GLM5Config) -> None:
        c = config
        who = "GLM5LM"
        deepseek_v3.check_share(who, c)
        if c.rope_scaling is not None:
            Log.fatal(f"{who}: rope_scaling {c.rope_scaling} is not "
                      "supported: the rotary is plain")
        if not c.norm_topk_prob or c.n_shared_experts < 1:
            Log.fatal(f"{who}: norm_topk_prob false or n_shared_experts "
                      f"{c.n_shared_experts} is not supported: gates are "
                      "normalised and an expert layer has a shared expert")
        if c.pool_width % c.index_head_dim \
                or not c.qk_rope_head_dim <= c.index_head_dim \
                or c.index_topk < 1:
            Log.fatal(f"{who}: index keys of {c.index_head_dim} do not "
                      f"tile a {c.pool_width}-wide pool row, hold the "
                      f"{c.qk_rope_head_dim} rotated values, or index_topk "
                      f"{c.index_topk} is under 1")
        self.config = config
        self.version = 0
        self.params = init_params(config)
        self._probes = {}       # attended_positions' programs, a shape

    def snapshot_params(self) -> Tuple[Dict[str, Any], int]:
        return self.params, self.version

    def logits(self, tokens: np.ndarray) -> jax.Array:
        """Full causal forward pass of ONE sequence: the prefill chunk's
        program over one chunk that holds it all, in a pool of its own."""
        cfg = self.config
        T, Bs = len(tokens), _FORWARD_BLOCK
        M = -(-T // Bs)
        pool = jnp.zeros((cfg.n_sublayers, M + 1, Bs, cfg.pool_width),
                         cfg.dtype)
        counters = jnp.zeros((max(cfg.n_expert_layers, 1) + 1,
                              COUNT_SCALARS + cfg.n_routed_experts + 1),
                             jnp.float32)
        toks = jnp.zeros((M * Bs,), jnp.int32).at[:T].set(
            jnp.asarray(tokens, jnp.int32))
        logits = _forward(cfg, self.params, pool, counters,
                          jnp.arange(1, M + 1, dtype=jnp.int32)[None], toks,
                          M * Bs)
        return logits[:T]

    def attended_positions(self, tokens: np.ndarray, queries: np.ndarray, *,
                           block_size: int, cache_len: int,
                           chunk: int) -> np.ndarray:
        """The positions that decode queries of ONE sequence attend in each
        layer, chosen by the served programs' own functions: the first
        ``max(queries)`` of ``tokens`` prefilled by
        :func:`prefill_chunk_paged`, ``chunk`` rows at a time, into a pool
        of its own (blocks of ``block_size``, ``cache_len`` positions, as
        the engine's), then ONE :func:`decode_step_paged` with the index
        kernel the served step takes, a slot a query: the token at each
        position of ``queries`` [Q] at its position. A check of the
        selection beside the served tokens. Returns ``[L, Q, K]`` int32,
        -1 in a column nothing was read by."""
        cfg = self.config
        key = (block_size, cache_len)
        if key not in self._probes:
            self._probes[key] = _probe_programs(cfg, block_size, cache_len)
        chunk_program, step_program = self._probes[key]
        M = -(-cache_len // block_size)
        pool = jnp.zeros((cfg.n_sublayers, M + 1, block_size,
                          cfg.pool_width), cfg.dtype)
        counters = jnp.zeros((max(cfg.n_expert_layers, 1) + 1,
                              COUNT_SCALARS + cfg.n_routed_experts + 1),
                             jnp.float32)
        tables = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
        tokens = np.asarray(tokens, np.int32)
        queries = np.asarray(queries, np.int32)
        end = int(queries.max())
        for offset in range(0, end, chunk):
            n = min(chunk, end - offset)
            rows = np.zeros((chunk,), np.int32)
            rows[:n] = tokens[offset:offset + n]
            pool, counters, _ = chunk_program(
                self.params, pool, counters, tables, 0, rows, offset, n)
        Q = len(queries)
        out = step_program(self.params, pool, counters,
                           jnp.broadcast_to(tables, (Q, M)),
                           tokens[queries], queries, np.ones((Q,), bool))
        return np.asarray(out[-1])

    def serving_programs(self, spec):
        """The engine's seam: ``longcat.latent_pool_programs`` over this
        model's walk; programs ``jit_glm5_decode_step``,
        ``jit_glm5_prefill_chunk``, ``jit_glm5_cow_block``."""
        cfg = self.config
        step = functools.partial(
            decode_step_paged, index_kernel=dsa.step_index_scores(
                cfg.dtype, spec.block_size, cfg.pool_width,
                cfg.index_head_dim))
        return longcat.latent_pool_programs(
            cfg, spec, "glm5", step, prefill_chunk_paged,
            (max(cfg.n_expert_layers, 1) + 1,
             COUNT_SCALARS + cfg.n_routed_experts + 1),
            lambda delta: counters_summary(cfg, delta))


def _probe_programs(cfg: GLM5Config, block_size: int, cache_len: int):
    """:meth:`GLM5LM.attended_positions`'s two programs: the served
    chunk's function, and the served step's with its selection
    returned."""
    kernel = dsa.step_index_scores(cfg.dtype, block_size, cfg.pool_width,
                                   cfg.index_head_dim)
    chunk = lambda *args: prefill_chunk_paged(cfg, *args, cache_len)
    step = lambda *args: decode_step_paged(cfg, *args, cache_len,
                                           index_kernel=kernel,
                                           with_selection=True)
    return jax.jit(chunk), jax.jit(step)


@functools.partial(jax.jit, static_argnums=(0, 6))
def _forward(cfg, params, pool, counters, tables, tokens, t):
    """Every row's logits [t, V] of ``t`` tokens from position 0: one
    chunk's walk (:func:`_chunk_walk`) without the chunk's tail."""
    rows = longcat.chunk_rows(pool, tables, 0, t, 0, t, t)
    h, _, _ = _chunk_walk(cfg, params, pool, counters, rows, tokens, t)
    return longcat._logits(cfg, params, h)
