"""A ``mellum`` language model on the serving path (first served:
Mellum2-12B-A2.5B): grouped-query attention of two kinds over two kinds
of cache, and a softmax top-k router over experts, one chip holding
whole layers.

Layer ``l`` is a ``full_attention`` or a ``sliding_attention`` layer as
``layer_types`` says; every layer's MLP is the expert layer. With ``N``
an RMSNorm with its own gain at each use::

    h' = h  + A_l(N(h))
    y  = h' + MoE(N(h'))        MoE(u) = sum over the top k of g_e E_e(u)

* **Attention.** ``q = a W_q`` (``H`` heads), ``k = a W_k``, ``v = a
  W_v`` (``KV`` heads), all of ``head_dim``; no bias, no query/key norm.
  Rotary over the whole head, rotate-half: a sliding layer's at the
  default frequencies, a full layer's at YaRN's
  (:func:`longcat.rope_frequencies`) with cos and sin times
  ``attention_factor``, so its scores carry the factor squared. Head
  ``h`` reads KV head ``h // (H / KV)``; scores ``q . k / sqrt(head_dim)``,
  causal; on a sliding layer key ``j`` is visible to query ``i`` only
  where ``i - j < sliding_window``. Softmax in float32.
* **The experts** (:func:`ops.moe.route_topk` with ``normalize``, then
  :func:`ops.moe.held_expert_layer` over all of them):
  ``p = softmax(float32(u W_r))``, the top ``num_experts_per_tok``,
  gates ``p / sum(p of the picks)``; no shared expert, no router bias.

**Two kinds of cache** (``serving/programs.py``). A token's cache row in
a layer is ``[V | K]``, ``KV * head_dim`` values each, after rotation
(1,024 values at the published sizes). The full layers keep theirs in
pool 0, a block pool ``[full layers, N + 1, Bs, 2 KV dh]``. The sliding
layers keep a RING a slot, a slot pool ``[sliding layers, slots,
sliding_window, 2 KV dh]``: position ``p`` lies in row ``p %
sliding_window``, so a slot holds the same bytes whatever its context.
A step writes its row, then attends over ``min(pos + 1, window)``
rows; the ring's order does not matter to a softmax. A prefill chunk
attends over the rows the ring holds from the slot's earlier chunks,
each at its own position, and its own rows under the position mask,
and only THEN writes its rows into the ring (written first, they would
overwrite keys that the chunk's earlier queries still need). A chunk
that starts a prompt (``offset == 0``) reads no ring row: a row is live
only where a position of this request lies in it.

**One kernel for both kinds in the step**: :func:`ops.paged_attention.
paged_mq_attention` in its shared mode (values the first ``KV dh``
columns of a row) with each head's query placed in its KV group's key
columns (the rest zeros), the way GPT-2's step spreads its heads over
``d_model``: a head's scores are its group's keys', its output the
group's slice of the ``[H, KV dh]`` result. The ring reaches the kernel
as blocks of ``ring_block`` rows named by a fixed table; its calls carry
the op name :data:`SWA_KERNEL`, so that a device trace tells the two
kinds apart.

Neither a prefix hit nor a speculative window can be served: a block
restores no ring, and a rejected draft would have overwritten ring
rows, so both are refused by name (:meth:`MellumLM.serving_programs`).
Not here: the multi-token-prediction head the model card names (no key
of the configuration describes it, and the served forward pass does not
run it). Weights are drawn ON THE DEVICE leaf by leaf from one law
(:func:`init_params`); the model is serve-only and its snapshot is the
weights themselves.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops import paged_attention as paged_kernel
from ..ops.moe import COUNT_SCALARS, held_expert_layer, route_topk
from . import deepseek_v3, longcat
from .longcat import rmsnorm

_NEG_INF = -1e30
# the sliding layers' kernel calls, by name in a device trace
SWA_KERNEL = "swa_paged_attention"
# rows of a ring block as the step's kernel copies them: one copy of
# 512 rows x 1,024 values is 1 MB
_RING_BLOCK_MAX = 512
# columns of the counters' last row (the attention's own): positions a
# sliding layer and a full layer read in the steps, and the tokens
_SLIDING_READ, _FULL_READ, _STEP_TOKENS = 0, 1, 2
_KINDS = ("full_attention", "sliding_attention")


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28          # layers held here
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)
    sliding_window: int = 1024
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    # by layer kind: {"rope_type": "default" | "yarn", "rope_theta", and
    # for YaRN factor, original_max_position_embeddings, beta_fast,
    # beta_slow and (optional) attention_factor}
    rope_parameters: Optional[Dict[str, Dict[str, Any]]] = None
    dtype: Any = jnp.bfloat16
    seed: int = 0

    def is_full(self, l: int) -> bool:
        return self.layer_types[l] == "full_attention"

    @property
    def n_sublayers(self) -> int:
        """Full layers: pool 0's depth."""
        return sum(self.is_full(l) for l in range(self.num_hidden_layers))

    @property
    def n_sliding(self) -> int:
        """Sliding layers: the ring pool's depth."""
        return self.num_hidden_layers - self.n_sublayers

    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def pool_width(self) -> int:
        """A cache row, ``[V | K]``."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def ring_block(self) -> int:
        """Rows of a ring block as the step's kernel sees the ring."""
        return math.gcd(self.sliding_window, _RING_BLOCK_MAX)


def config_from_dict(cfg: dict, seed: int) -> MellumConfig:
    """From a configuration file's dict (the published key names;
    ``layer_types`` cut to the ``num_hidden_layers`` held here). Refused
    by name: an MLP that is not the expert layer, a bias on the
    attention's projections, unnormalised gates and a rotary type other
    than ``default`` and ``yarn``."""
    L = int(cfg["num_hidden_layers"])
    mlp = list(cfg.get("mlp_layer_types", ["sparse"] * L))[:L]
    if any(kind != "sparse" for kind in mlp):
        Log.fatal(f"MellumLM: mlp_layer_types {mlp} is not supported: every "
                  "layer held here is an expert layer")
    if cfg.get("attention_bias", False):
        Log.fatal("MellumLM: attention_bias is not supported")
    if not cfg.get("norm_topk_prob", True):
        Log.fatal("MellumLM: norm_topk_prob false is not supported: the "
                  "picks' gates are normalised to sum 1")
    for kind, rope in cfg["rope_parameters"].items():
        if rope.get("rope_type", "default") not in ("default", "yarn"):
            Log.fatal(f"MellumLM: rope_type {rope['rope_type']!r} of the "
                      f"{kind} layers is not supported")
    names = {f.name for f in dataclasses.fields(MellumConfig)}
    kw = {k: v for k, v in cfg.items() if k in names and k != "dtype"}
    kw["layer_types"] = tuple(cfg["layer_types"][:L])
    return MellumConfig(dtype=jnp.dtype(cfg.get("dtype", "bfloat16")),
                        seed=int(seed), **kw)


# -- the weight law -----------------------------------------------------------
# As deepseek_v3.py's: a key per (layer, leaf) from the seed, and per expert
# by its index; float32 normal on the device times the leaf's std, rounded
# to the configuration's dtype; the router stays float32. The reference
# keeps its own copy. std is 1/sqrt(fan_in) but: W_q 1.5 times it (scores
# of std near 1.5 on a sliding layer, 2.4 on a full one under YaRN's
# factor); W_o 8 times it (a head's output is a softmax average over ~100
# effective keys, 0.1 an element: attention then adds ~0.8 an element to
# the stream); an expert's W_down 2.5 times it (normalised gates over 8
# picks average the experts: their sum adds ~0.6 an element, as an FFN)
_LEAVES = ("embed", "head", "w_q", "w_k", "w_v", "w_o", "router",
           "e_gate", "e_up", "e_down")
_Q_GAIN, _WO_GAIN, _DOWN_GAIN = 1.5, 8.0, 2.5


def _leaf_key(seed: int, layer: int, leaf: str):
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
                             layer + 1)
    return jax.random.fold_in(key, _LEAVES.index(leaf))


def init_params(cfg: MellumConfig) -> Dict[str, Any]:
    """The weights, each leaf one jitted draw on the default device
    (never the whole tree at once, never on the host)."""
    D, H, KV, dh = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.num_key_value_heads, cfg.head_dim
    E, F, dt = cfg.num_experts, cfg.moe_intermediate_size, cfg.dtype
    draw, draw_experts = deepseek_v3.drawers(dt)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for l in range(cfg.num_hidden_layers):
        k = lambda leaf, l=l: _leaf_key(cfg.seed, l, leaf)
        keys = lambda leaf: jax.vmap(
            lambda e: jax.random.fold_in(k(leaf), e))(jnp.arange(E))
        layers.append({
            "attn": {"norm": ones(D),
                     "w_q": draw(k("w_q"), (D, H * dh), _Q_GAIN * D ** -0.5,
                                 dt),
                     "w_k": draw(k("w_k"), (D, KV * dh), D ** -0.5, dt),
                     "w_v": draw(k("w_v"), (D, KV * dh), D ** -0.5, dt),
                     "w_o": draw(k("w_o"), (H * dh, D),
                                 _WO_GAIN * (H * dh) ** -0.5, dt)},
            "ffn_norm": ones(D),
            "router": draw(k("router"), (D, E), D ** -0.5, jnp.float32),
            "experts": {
                "w_gate": draw_experts(keys("e_gate"), (D, F), D ** -0.5),
                "w_up": draw_experts(keys("e_up"), (D, F), D ** -0.5),
                "w_down": draw_experts(keys("e_down"), (F, D),
                                       _DOWN_GAIN * F ** -0.5)}})
    return {"embed": draw(_leaf_key(cfg.seed, -1, "embed"),
                          (cfg.vocab_size, D), 1.0, dt),
            "head": draw(_leaf_key(cfg.seed, -1, "head"),
                         (D, cfg.vocab_size), D ** -0.5, dt),
            "final_norm": ones(D),
            "layers": layers}


# -- a layer ------------------------------------------------------------------
def rotary(cfg: MellumConfig, full: bool) -> Tuple[jax.Array, float]:
    """``(inv [head_dim / 2], cos/sin scale)`` of a layer kind: YaRN's
    frequencies and ``attention_factor`` (0.1 ln(factor) + 1 where the
    configuration gives none) where its ``rope_type`` is ``yarn``."""
    rope = cfg.rope_parameters[_KINDS[0] if full else _KINDS[1]]
    yarn = rope.get("rope_type", "default") == "yarn"
    freqs = longcat.rope_frequencies(types.SimpleNamespace(
        qk_rope_head_dim=cfg.head_dim, rope_theta=float(rope["rope_theta"]),
        rope_scaling=rope if yarn else None))
    scale = float(rope.get("attention_factor")
                  or 0.1 * math.log(rope["factor"]) + 1.0) if yarn else 1.0
    return freqs, scale


def rope_half(x, pos, inv, scale: float = 1.0):
    """Rotate-half rotary: ``x`` [T, ..., d] float32, the pair
    ``(x[i], x[i + d/2])`` turned by ``pos * inv[i]``, cos and sin times
    ``scale``."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]        # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(cfg: MellumConfig, w, x, pos, full: bool):
    """Queries ``[T, H, dh]`` and cache rows ``[T, 2 KV dh]`` (``[V |
    K]``, K rotated) of the normed ``x`` [T, D] at positions ``pos``, in
    the model's dtype."""
    f32, dt = jnp.float32, cfg.dtype
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    T = x.shape[0]
    inv, scale = rotary(cfg, full)
    q = jnp.dot(x, w["w_q"], preferred_element_type=f32).reshape(T, H, dh)
    k = jnp.dot(x, w["w_k"], preferred_element_type=f32).reshape(T, KV, dh)
    v = jnp.dot(x, w["w_v"], preferred_element_type=f32)
    k = rope_half(k, pos, inv, scale).astype(dt).reshape(T, KV * dh)
    return (rope_half(q, pos, inv, scale).astype(dt),
            jnp.concatenate([v.astype(dt), k], -1))


def _out(cfg, w, o):
    """Heads' outputs ``[T, H, dh]`` through ``W_o``: [T, D] float32."""
    return jnp.dot(o.reshape(o.shape[0], -1).astype(cfg.dtype), w["w_o"],
                   preferred_element_type=jnp.float32)


def attend_rows(cfg: MellumConfig, w, q, rows, mask):
    """Queries ``q`` [C, H, dh] against cache rows [T, 2 KV dh] where
    ``mask`` [C, T]: head ``h`` reads KV head ``h // (H / KV)``. Returns
    the sublayer's output [C, D] float32."""
    f32, dt = jnp.float32, cfg.dtype
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    C, T = q.shape[0], rows.shape[0]
    v = rows[:, :KV * dh].reshape(T, KV, dh)
    k = rows[:, KV * dh:].reshape(T, KV, dh)
    qg = q.reshape(C, KV, H // KV, dh)
    s = jnp.einsum("cgqd,tgd->gqct", qg, k, preferred_element_type=f32) \
        / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)
    o = jnp.einsum("gqct,tgd->cgqd", p.astype(dt), v,
                   preferred_element_type=f32)
    return _out(cfg, w, o.reshape(C, H, dh))


def _attend_each(cfg: MellumConfig, w, q, rows, live):
    """The step's gathered form: slot ``s``'s query ``q[s]`` [H, dh]
    against its own rows ``rows[s]`` [T, 2 KV dh] where ``live[s]``:
    [S, D] float32."""
    return jax.vmap(lambda qs, rs, m: attend_rows(
        cfg, w, qs[None], rs, m[None])[0])(q, rows, live)


def group_query(cfg: MellumConfig, q):
    """The step kernel's query ``[S, H, 2 KV dh]``: zeros over the value
    columns, then head ``h``'s query in its KV group's key columns and
    zeros in the others."""
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    S = q.shape[0]
    spread = jnp.einsum("sgqd,gh->sgqhd", q.reshape(S, KV, H // KV, dh),
                        jnp.eye(KV, dtype=q.dtype)).reshape(S, H, KV * dh)
    return jnp.concatenate([jnp.zeros_like(spread), spread], -1)


def group_output(cfg: MellumConfig, o):
    """The kernel's ``[S, H, KV dh]`` (each head against every group's
    values) cut to each head's own group: [S, H, dh]."""
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    S = o.shape[0]
    return jnp.einsum("sgqhd,gh->sgqd", o.reshape(S, KV, H // KV, KV, dh),
                      jnp.eye(KV, dtype=o.dtype)).reshape(S, H, dh)


def expert_layer(cfg: MellumConfig, layer, u, valid=None):
    """``(y [T, D] float32, counts)``: the softmax top-k router with
    normalised gates over all the experts, every one held here."""
    E = cfg.num_experts
    idx, gates = route_topk(u, layer["router"], jnp.zeros((E,), jnp.float32),
                            cfg.num_experts_per_tok, 1.0, normalize=True)
    return held_expert_layer(u, idx, gates, layer["experts"], E, 0,
                             identity=False, valid=valid)


def layer_apply(cfg: MellumConfig, layer, h, attend, valid=None):
    """One layer on the residual stream ``h`` [T, D] (float32);
    ``attend(w, x)`` runs its attention on the normed input. Returns
    ``(h, counts)``."""
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    w = layer["attn"]
    h = h + attend(w, rmsnorm(h, w["norm"], eps, dt))
    y, counts = expert_layer(cfg, layer, rmsnorm(h, layer["ffn_norm"], eps,
                                                 dt), valid)
    return h + y, counts


def forward(cfg: MellumConfig, params, tokens) -> jax.Array:
    """Full causal forward pass of ONE sequence ``tokens`` [T], no
    cache: logits [T, V] in float32."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    d = pos[:, None] - pos[None, :]
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for l, layer in enumerate(params["layers"]):
        full = cfg.is_full(l)
        mask = (d >= 0) & (full | (d < cfg.sliding_window))

        def attend(w, x, full=full, mask=mask):
            q, rows = project(cfg, w, x, pos, full)
            return attend_rows(cfg, w, q, rows, mask)

        h, _ = layer_apply(cfg, layer, h, attend)
    return longcat._logits(cfg, params, h)


# -- paged programs -------------------------------------------------------------
def _walk(cfg, params, h, pool, ring, counters, full_attend, sliding_attend,
          valid):
    """Every layer over ``h``; ``full_attend(w, x, pool, sub) -> (pool,
    out)`` runs full layer ``sub`` against pool 0, ``sliding_attend(w,
    x, ring, j) -> (ring, out)`` sliding layer ``j`` against the ring.
    A layer's routing counts go to its row of ``counters``."""
    sub = j = 0
    for l, layer in enumerate(params["layers"]):
        def attend(w, x, l=l, sub=sub, j=j):
            nonlocal pool, ring
            if cfg.is_full(l):
                pool, out = full_attend(w, x, pool, sub)
            else:
                ring, out = sliding_attend(w, x, ring, j)
            return out

        h, counts = layer_apply(cfg, layer, h, attend, valid)
        counters = counters.at[l].add(counts)
        sub, j = (sub + 1, j) if cfg.is_full(l) else (sub, j + 1)
    return h, pool, ring, counters


def decode_step_paged(cfg: MellumConfig, params, pool, ring, counters,
                      block_tables, tok, pos, active, t_logical: int,
                      paged_attention=None, ring_attention=None):
    """One fused token step over S slots: the full layers against the
    paged pool (``longcat.decode_step_paged``'s contract), the sliding
    layers against ``ring`` [sliding layers, S, window, 2 KV dh]: a live
    slot writes its row at ``pos % window``, then attends over
    ``min(pos + 1, window)`` rows; a slot that is not ``active`` leaves
    its ring bit-identical. ``paged_attention`` / ``ring_attention``
    (``ops.paged_attention.paged_mq_attention``, the second under the
    name :data:`SWA_KERNEL`, where the model's ``serving_programs`` finds
    it applies) read the rows where they lie; without them the step
    gathers the full layers' view and reads the ring whole. Returns
    ``(pool, ring, counters, next_tok, pos)``."""
    S = tok.shape[0]
    n_sub, N, Bs, Wd = pool.shape
    W, Br = cfg.sliding_window, cfg.ring_block
    rows = longcat.step_rows(pool, block_tables, pos, active)
    lengths = rows[2]
    ring_len = jnp.minimum(lengths, W)
    # a dead lane's ring row is dropped (an index past the slots)
    ring_slot = jnp.where(active, jnp.arange(S), S)
    ring_tables = (jnp.arange(S)[:, None] * (W // Br)
                   + jnp.arange(W // Br)[None, :])
    h = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)

    def full_attend(w, x, pool, sub):
        q, row = project(cfg, w, x, pos, True)
        pool = pool.at[sub, rows[0], rows[1]].set(row)
        if paged_attention is not None:
            o = paged_attention(group_query(cfg, q),
                                pool.reshape(n_sub * N, Bs, Wd), None,
                                sub * N + block_tables, lengths,
                                scale=cfg.head_dim ** -0.5, wv=Wd // 2)
            return pool, _out(cfg, w, group_output(cfg, o))
        live = jnp.arange(t_logical)[None, :] <= pos[:, None]
        return pool, _attend_each(cfg, w, q, longcat._view(
            pool, sub, block_tables, t_logical), live)

    def sliding_attend(w, x, ring, j):
        q, row = project(cfg, w, x, pos, False)
        ring = ring.at[j, ring_slot, pos % W].set(row, mode="drop")
        if ring_attention is not None:
            o = ring_attention(group_query(cfg, q),
                               ring.reshape(-1, Br, Wd), None,
                               j * S * (W // Br) + ring_tables, ring_len,
                               scale=cfg.head_dim ** -0.5, wv=Wd // 2)
            return ring, _out(cfg, w, group_output(cfg, o))
        live = jnp.arange(W)[None, :] < ring_len[:, None]
        return ring, _attend_each(cfg, w, q, ring[j], live)

    h, pool, ring, counters = _walk(cfg, params, h, pool, ring, counters,
                                    full_attend, sliding_attend, active)
    f32 = jnp.float32
    counters = counters.at[-1, :3].add(jnp.stack([
        jnp.sum(ring_len.astype(f32)), jnp.sum(lengths.astype(f32)),
        jnp.sum(active.astype(f32))]))
    return (pool, ring, counters) + longcat.greedy_next(cfg, params, h, tok,
                                                        pos, active)


def prefill_chunk_paged(cfg: MellumConfig, params, pool, ring, counters,
                        block_tables, slot, tokens, offset, length,
                        t_logical: int):
    """Incremental prefill of one fixed-size chunk of ONE slot
    (``longcat.prefill_chunk_paged``'s contract). A full layer writes
    its rows into the pool and attends over the slot's blocks; a sliding
    layer attends over the ring's rows that hold a position of this
    request before ``offset`` (row ``r`` holds the last such position
    ``= r mod window``, none where that is negative) and over the
    chunk's own rows, key ``j`` visible to query ``i`` where ``0 <= p_i
    - p_j < window``, THEN writes the chunk's last ``window`` valid rows
    into the ring. A chunk whose table names only the scratch block
    (warm-up) writes no ring row. Returns ``(pool, ring, counters,
    last_logits [V])``."""
    C = tokens.shape[0]
    S, W = ring.shape[1], cfg.sliding_window
    rows = longcat.chunk_rows(pool, block_tables, slot, C, offset, length,
                              t_logical)
    bt_row, pos_ix, valid, blk_ix, off, mask = rows
    held = offset - 1 - jnp.mod(offset - 1 - jnp.arange(W), W)
    key_pos = jnp.concatenate([held, pos_ix])
    key_live = jnp.concatenate([held >= 0, valid])
    d = pos_ix[:, None] - key_pos[None, :]
    window_mask = key_live[None, :] & (d >= 0) & (d < W)
    # the rows the ring keeps: valid, the last ``window`` of them, and
    # not warm-up's (an index past the slots is dropped)
    keep = valid & (jnp.arange(C) >= length - W) & (bt_row[0] != 0)
    ring_slot = jnp.where(keep, slot, S)
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def full_attend(w, x, pool, sub):
        q, new = project(cfg, w, x, pos_ix, True)
        pool = pool.at[sub, blk_ix, off].set(new)
        view = longcat._view(pool, sub, bt_row, t_logical)
        return pool, attend_rows(cfg, w, q, view, mask)

    def sliding_attend(w, x, ring, j):
        q, new = project(cfg, w, x, pos_ix, False)
        keys = jnp.concatenate([ring[j, slot], new])
        out = attend_rows(cfg, w, q, keys, window_mask)
        return ring.at[j, ring_slot, pos_ix % W].set(new, mode="drop"), out

    h, pool, ring, counters = _walk(cfg, params, h, pool, ring, counters,
                                    full_attend, sliding_attend, valid)
    return pool, ring, counters, longcat.last_logits(cfg, params, h, length)


def counters_summary(cfg: MellumConfig, counts: np.ndarray) -> dict:
    """``eng.stats()``'s keys from the accumulated counters: the expert
    layers' rows as ``longcat.routing_summary`` reads them, and from the
    last row the steps' ``swa_step_tokens`` (a live slot and step each),
    ``swa_sliding_positions`` and ``swa_full_positions`` (positions ONE
    sliding layer, ONE full layer read over them) and their ratio
    ``swa_read_share`` (1.0: the window never bit)."""
    c = np.asarray(counts, np.float64)
    out = longcat.routing_summary(cfg, c[:-1])
    own = c[-1]
    out["swa_step_tokens"] = float(own[_STEP_TOKENS])
    out["swa_sliding_positions"] = float(own[_SLIDING_READ])
    out["swa_full_positions"] = float(own[_FULL_READ])
    if own[_FULL_READ] > 0:
        out["swa_read_share"] = float(own[_SLIDING_READ] / own[_FULL_READ])
    return out


class MellumLM:
    """Serve-only ``mellum`` model: weights drawn on the device from
    ``config.seed``; the snapshot contract and the engine's seam as
    :class:`longcat.LongCatLM` has them."""

    def __init__(self, config: MellumConfig) -> None:
        c = config
        who = "MellumLM"
        if len(c.layer_types) != c.num_hidden_layers \
                or set(c.layer_types) - set(_KINDS):
            Log.fatal(f"{who}: layer_types {list(c.layer_types)} is not "
                      f"{c.num_hidden_layers} of {_KINDS}")
        if c.n_sublayers < 1 or c.n_sliding < 1:
            Log.fatal(f"{who}: layer_types {list(c.layer_types)} hold no "
                      "layer of one of the two attention kinds")
        if c.num_attention_heads % c.num_key_value_heads:
            Log.fatal(f"{who}: {c.num_attention_heads} query heads do not "
                      f"make groups over {c.num_key_value_heads} KV heads")
        if not 0 < c.num_experts_per_tok <= c.num_experts:
            Log.fatal(f"{who}: num_experts_per_tok {c.num_experts_per_tok} "
                      f"outside the {c.num_experts} experts")
        self.config = config
        self.version = 0
        self.params = init_params(config)

    def snapshot_params(self) -> Tuple[Dict[str, Any], int]:
        return self.params, self.version

    def logits(self, tokens: np.ndarray) -> jax.Array:
        return forward(self.config, self.params,
                       jnp.asarray(tokens, jnp.int32))

    def serving_programs(self, spec):
        """The engine's seam: ``longcat.latent_pool_programs`` over this
        model's walk, pool 0 the full layers' K/V rows, one slot pool
        the sliding layers' rings (bfloat16); programs
        ``jit_mellum2_decode_step`` and ``jit_mellum2_prefill_chunk``.
        Refused by name beside what every such model refuses:
        ``prefix_cache`` and (with its own reason) ``spec_k``."""
        cfg = self.config
        ring_attention = paged_kernel.step_attention(
            cfg.dtype, cfg.ring_block, cfg.pool_width)
        if ring_attention is not None:
            ring_attention = functools.partial(ring_attention,
                                               name=SWA_KERNEL)
        step = functools.partial(decode_step_paged,
                                 ring_attention=ring_attention)
        return longcat.latent_pool_programs(
            cfg, spec, "mellum2", step, prefill_chunk_paged,
            (cfg.num_hidden_layers + 1, COUNT_SCALARS + cfg.num_experts),
            lambda delta: counters_summary(cfg, delta),
            slot_pools=(((cfg.n_sliding, spec.slots, cfg.sliding_window,
                          cfg.pool_width),
                         cfg.dtype),),
            lacking={"prefix_cache": "a block hit restores no sliding "
                     "window's ring", "spec_k": "a rejected draft would "
                     "have overwritten ring rows, and there is no verify "
                     "step"})
