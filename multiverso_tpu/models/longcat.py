"""LongCat-Flash-Chat's block on the serving path, one chip's share.

A "layer" of the published model is a double block: two latent-attention
(MLA) sublayers, two dense SwiGLU FFNs and ONE shortcut-connected expert
layer (top-12 of 768 router outputs: 512 SwiGLU experts and 256 identity
"zero-compute" experts). With ``N`` an RMSNorm with its own gain at each
use::

    h1 = x  + MLA_0(N(x));   u = N(h1);   m = MoE(u)
    h2 = h1 + FFN_0(u)
    h3 = h2 + MLA_1(N(h2))
    y  = h3 + FFN_1(N(h3)) + m

The expert layer reads the first sublayer's output and lands after the
second FFN (the shortcut). This chip holds ``n_routed_experts`` of the
``published.n_routed_experts`` FFN experts starting at ``expert_offset``
(:func:`ops.moe.held_expert_layer`), every identity expert, attention and
the dense FFNs whole, and a slice of the vocabulary; what the absent
experts would add is left out.

**Two paths for one MLA sublayer.** The cache row of a token in a
sublayer is ``[c (kv_lora_rank), k_rope (qk_rope_head_dim)]`` after norm,
scale and rotation, 576 values: one paged pool ``[2 * blocks, N + 1, Bs,
640]`` (:attr:`LongCatConfig.pool_width`: the row padded with zeros to
whole 128-lane tiles; compiled for a described v5e, a 576-wide pool gets
a second, pool-major layout and copies between the two: 3.8 GB of
temporaries a step where this one has 0.57 GB, and the chunk is refused
for memory, so that width was never timed on the chip).
A prefill chunk expands the slot's rows to per-head keys and values
(``c W_kvb``) and attends causally; the decode step attends IN THE
LATENT: ``W_kvb``'s key half is absorbed into the query
(``q~_h = q_nope_h W_kvb[k,h]^T``) and its value half into the output
(``(P c) W_kvb[v,h]``), so a step reads 576 values a token and not
``heads x (192 + 128)``. :func:`mla_expanded` and :func:`mla_latent` are
the two forms; a test holds them equal.

**One copy for two models.** ``models/deepseek_v3.py`` (PR 32) serves
over the same latent pool with this module's MLA, norm, rotary, pool
view, per-sublayer attention against the pool (:func:`step_attend`,
:func:`chunk_attend`), copy-on-write and seam builder
(:func:`latent_pool_programs`). They take what differs between the two
from the configuration they are handed: ``mla_scale_q_lora`` /
``mla_scale_kv_lora``, ``rope_scaling`` (:func:`rope_frequencies`) and
``softmax_divisor``. They stay HERE, under these names, because the
benchmark's accepted tests plant their faults by these names
(``benchmarks/tests/test_longcat_cell.py`` patches ``longcat.rope``,
``route_topk``, ``held_expert_layer``, ``config_from_dict``).

Weights are drawn ON THE DEVICE, leaf by leaf, from one law
(:func:`init_params`); the model is serve-only, its weights never move,
so its snapshot is the weights themselves (no copy: 10 GB cannot exist
twice on a 16 GB chip). The engine takes the cache layout and the paged
programs through :meth:`LongCatLM.serving_programs`
(``serving/programs.py``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops import paged_attention as paged_kernel
from ..ops.moe import COUNT_SCALARS, held_expert_layer, route_topk, swiglu

_NEG_INF = -1e30


class LatentCacheSizes:
    """What a latent pool's shape follows from, for any configuration
    with ``kv_lora_rank``, ``qk_rope_head_dim`` and
    ``max_position_embeddings``."""

    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """A cache row as the pool holds it: whole 128-lane tiles."""
        return -(-self.cache_width // 128) * 128


@dataclasses.dataclass(frozen=True)
class LongCatConfig(LatentCacheSizes):
    vocab_size: int = 131072           # rows held here (a slice)
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28               # double blocks held here
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512        # FFN experts HELD here
    total_routed_experts: int = 512    # FFN experts the router addresses
    expert_offset: int = 0             # first held expert's router output
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    dtype: Any = jnp.bfloat16
    seed: int = 0

    @property
    def n_sublayers(self) -> int:
        return 2 * self.num_layers

    @property
    def router_outputs(self) -> int:
        return self.total_routed_experts + self.zero_expert_num

    # what the MLA code below takes from whichever configuration it is
    # handed (``models/deepseek_v3.py`` hands it another): the two
    # ``mla_scale_*`` switches above, the rotary frequencies' scaling
    # (none here), a head-wise sigmoid gate on the attention output
    # before W_o (leaf ``w_og``; none here) and what the attention scores
    # are divided by
    rope_scaling = None
    mla_head_gate = False

    @property
    def softmax_divisor(self) -> float:
        return math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)


def share_config(cls, cfg: dict, seed: int):
    """``cls`` from a configuration file's dict (HF key names;
    ``n_routed_experts`` is what is held here,
    ``published.n_routed_experts`` what the router addresses)."""
    names = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in cfg.items() if k in names and k != "dtype"}
    kw["total_routed_experts"] = int(
        cfg.get("published", {}).get("n_routed_experts",
                                     cfg["n_routed_experts"]))
    return cls(dtype=jnp.dtype(cfg.get("dtype", "bfloat16")),
               seed=int(seed), **kw)


def config_from_dict(cfg: dict, seed: int) -> LongCatConfig:
    return share_config(LongCatConfig, cfg, seed)


# -- the weight law -----------------------------------------------------------
# One key per (block, leaf) from the seed (and per expert, by its ROUTER
# OUTPUT index, so that every share of a layer draws the experts it holds
# and no others); float32 normal on the device, times the leaf's std,
# rounded to the configuration's dtype. The router stays float32. The
# reference keeps its own copy of this law.
_LEAVES = ("embed", "head", "w_qa", "w_qb", "w_kva", "w_kvb", "w_o",
           "w_gate", "w_up", "w_down", "router", "e_gate", "e_up", "e_down")
# W_qb at a quarter of 1/sqrt(fan_in) and the router at 1.2/sqrt(fan_in):
# attention scores then have a std near 1.5 and a token's 12 gates sum to
# about 1 (a flat softmax hides faults, a one-hot one flips on rounding)
_QB_GAIN = 0.25
_ROUTER_GAIN = 1.2


def _leaf_key(seed: int, block: int, leaf: str, sub: int = 0):
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
                             block + 1)
    return jax.random.fold_in(key, 16 * _LEAVES.index(leaf) + sub)


def _draw(key, shape, std: float, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def draw_mla(cfg, draw, key, qb_gain: float, wo_gain: float = 1.0):
    """One MLA sublayer's leaves: ``draw(key, shape, std, dtype)`` with
    ``key(leaf)`` the leaf's key; std ``1/sqrt(fan_in)`` times the two
    gains a model's law sets. Without a query latent (``q_lora_rank``
    None) the query is one projection ``w_q`` at ``qb_gain``; with
    ``mla_head_gate`` a ``w_og`` [D, H] makes the output gate's logits."""
    D, H, rq, rkv = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.dtype
    ones = lambda n: jnp.ones((n,), jnp.float32)
    if rq is None:
        query = {"w_q": draw(key("w_q"), (D, H * (dn + dr)),
                             qb_gain * D ** -0.5, dt)}
    else:
        query = {"q_norm": ones(rq),
                 "w_qa": draw(key("w_qa"), (D, rq), D ** -0.5, dt),
                 "w_qb": draw(key("w_qb"), (rq, H * (dn + dr)),
                              qb_gain * rq ** -0.5, dt)}
    if cfg.mla_head_gate:
        query["w_og"] = draw(key("w_og"), (D, H), D ** -0.5, dt)
    return {
        "norm": ones(D), "kv_norm": ones(rkv), **query,
        "w_kva": draw(key("w_kva"), (D, rkv + dr), D ** -0.5, dt),
        "w_kvb": draw(key("w_kvb"), (rkv, H * (dn + dv)), rkv ** -0.5, dt),
        "w_o": draw(key("w_o"), (H * dv, D), wo_gain * (H * dv) ** -0.5,
                    dt)}


def init_params(cfg: LongCatConfig) -> Dict[str, Any]:
    """The share's weights, each leaf one jitted draw on the default
    device (never the whole tree at once, never on the host)."""
    D, F, Fe = cfg.hidden_size, cfg.ffn_hidden_size, \
        cfg.expert_ffn_hidden_size
    dt = cfg.dtype
    draw = jax.jit(_draw, static_argnums=(1, 2, 3))
    draw_experts = jax.jit(
        lambda keys, shape, std: jax.vmap(
            lambda k: _draw(k, shape, std, dt))(keys),
        static_argnums=(1, 2))
    ones = lambda n: jnp.ones((n,), jnp.float32)

    def mla(b, j):
        return draw_mla(cfg, draw,
                        lambda leaf: _leaf_key(cfg.seed, b, leaf, j),
                        _QB_GAIN)

    def ffn(b, j):
        k = lambda leaf: _leaf_key(cfg.seed, b, leaf, j)
        return {"norm": ones(D),
                "w_gate": draw(k("w_gate"), (D, F), D ** -0.5, dt),
                "w_up": draw(k("w_up"), (D, F), D ** -0.5, dt),
                "w_down": draw(k("w_down"), (F, D), F ** -0.5, dt)}

    def experts(b):
        ids = cfg.expert_offset + jnp.arange(cfg.n_routed_experts)
        keys = lambda leaf: jax.vmap(
            lambda e: jax.random.fold_in(_leaf_key(cfg.seed, b, leaf), e))(ids)
        return {
            "w_gate": draw_experts(keys("e_gate"), (D, Fe), D ** -0.5),
            "w_up": draw_experts(keys("e_up"), (D, Fe), D ** -0.5),
            "w_down": draw_experts(keys("e_down"), (Fe, D), Fe ** -0.5)}

    blocks = []
    for b in range(cfg.num_layers):
        blocks.append({
            "mla": [mla(b, 0), mla(b, 1)],
            "ffn": [ffn(b, 0), ffn(b, 1)],
            "router": draw(_leaf_key(cfg.seed, b, "router"),
                           (D, cfg.router_outputs),
                           _ROUTER_GAIN * D ** -0.5, jnp.float32),
            "router_bias": jnp.zeros((cfg.router_outputs,), jnp.float32),
            "experts": experts(b)})
    return {
        # unit variance an element: an embedding of norm 1 would be
        # swamped by the first sublayer's output and every position
        # would carry the same running mean
        "embed": draw(_leaf_key(cfg.seed, -1, "embed"),
                      (cfg.vocab_size, D), 1.0, dt),
        "head": draw(_leaf_key(cfg.seed, -1, "head"),
                     (D, cfg.vocab_size), D ** -0.5, dt),
        "final_norm": ones(D),
        "blocks": blocks}


# -- the block's pieces ---------------------------------------------------------
def rmsnorm(x, g, eps: float, dtype):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g
    return y.astype(dtype)


def rope_frequencies(cfg):
    """The rotary slice's ``d/2`` angular frequencies, ``theta^(-2i/d)``;
    under ``cfg.rope_scaling`` (YaRN, as DeepSeek-V3 publishes it) pair
    ``i`` is slowed by ``factor`` to the degree ``r_i`` that it turns
    fewer than ``beta_fast`` (r = 0) down to ``beta_slow`` (r = 1) times
    over the original context: ``f_i = e_i (1 - r_i) + e_i / factor
    r_i``."""
    d, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if cfg.rope_scaling is None:
        return inv
    y = cfg.rope_scaling
    turns_at = lambda n: d * math.log(
        y["original_max_position_embeddings"] / (n * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(y["beta_fast"])), 0)
    high = min(math.ceil(turns_at(y["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / y["factor"] * ramp


def rope(x, pos, inv):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``pos * inv[i]`` (DeepSeek-V3's pairing; ``inv`` from
    :func:`rope_frequencies`). ``x`` [T, ..., d], ``pos`` [T]; float32
    in and out."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, d/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos],
                     axis=-1).reshape(x.shape)


def mla_project(cfg: LongCatConfig, w, x, pos, width: int = 0):
    """Queries and the cache rows of tokens ``x`` [T, D] (already
    normed) at positions ``pos``: ``(q_nope [T, H, dn], q_rope [T, H,
    dr], row [T, rkv + dr])``, all in the model's dtype; ``row`` is what
    the cache holds: ``[c, k_rope]`` after norm, scale and rotation
    (padded with zeros to ``width``, the pool's, where given)."""
    f32, dt = jnp.float32, cfg.dtype
    H, dn, dr = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    rq, rkv, D = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.hidden_size
    T = x.shape[0]
    skv = math.sqrt(D / rkv) if cfg.mla_scale_kv_lora else 1.0
    if rq is None:      # no query latent: one projection
        q = jnp.dot(x, w["w_q"], preferred_element_type=f32)
    else:
        sq = math.sqrt(D / rq) if cfg.mla_scale_q_lora else 1.0
        c_q = rmsnorm(jnp.dot(x, w["w_qa"], preferred_element_type=f32),
                      w["q_norm"] * sq, cfg.rms_norm_eps, dt)
        q = jnp.dot(c_q, w["w_qb"], preferred_element_type=f32)
    q = q.reshape(T, H, dn + dr)
    q_rope = rope(q[..., dn:], pos, rope_frequencies(cfg)).astype(dt)
    kv = jnp.dot(x, w["w_kva"], preferred_element_type=f32)
    c = rmsnorm(kv[:, :rkv], w["kv_norm"] * skv, cfg.rms_norm_eps, dt)
    k_rope = rope(kv[:, rkv:], pos, rope_frequencies(cfg)).astype(dt)
    pad = [jnp.zeros((T, width - rkv - dr), dt)] if width > rkv + dr else []
    return (q[..., :dn].astype(dt), q_rope,
            jnp.concatenate([c, k_rope] + pad, -1))


def _kvb(cfg: LongCatConfig, w):
    """``W_kvb`` as ``(key half [rkv, H, dn], value half [rkv, H, dv])``."""
    H, dn, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    wb = w["w_kvb"].reshape(cfg.kv_lora_rank, H, dn + dv)
    return wb[..., :dn], wb[..., dn:]


def head_gate(cfg, w, x):
    """``sigmoid(x w_og)`` [T, H] in float32, one scalar a head that
    multiplies the head's attention output before ``W_o``; None where
    the configuration has no such gate."""
    if not cfg.mla_head_gate:
        return None
    return jax.nn.sigmoid(jnp.dot(x, w["w_og"],
                                  preferred_element_type=jnp.float32))


def _gated(o, gate):
    return o if gate is None else o * gate[..., None]


def mla_expanded(cfg: LongCatConfig, w, q_nope, q_rope, rows, mask,
                 gate=None):
    """The expanded form: ``rows`` [T, >= rkv + dr] become per-head keys
    and values; queries [C, H, .] attend where ``mask`` [C, T]; ``gate``
    is :func:`head_gate`'s. Returns the sublayer's output [C, D] in
    float32."""
    f32, dt = jnp.float32, cfg.dtype
    rkv = cfg.kv_lora_rank
    wk, wv = _kvb(cfg, w)
    c, k_rope = rows[:, :rkv], rows[:, rkv:cfg.cache_width]
    k_nope = jnp.einsum("tc,chd->thd", c, wk,
                        preferred_element_type=f32).astype(dt)
    v = jnp.einsum("tc,chd->thd", c, wv,
                   preferred_element_type=f32).astype(dt)
    s = (jnp.einsum("qhd,thd->hqt", q_nope, k_nope,
                    preferred_element_type=f32)
         + jnp.einsum("qhr,tr->hqt", q_rope, k_rope,
                      preferred_element_type=f32)) / cfg.softmax_divisor
    p = jax.nn.softmax(jnp.where(mask[None], s, _NEG_INF), axis=-1)
    o = _gated(jnp.einsum("hqt,thd->qhd", p.astype(dt), v,
                          preferred_element_type=f32), gate).astype(dt)
    return jnp.dot(o.reshape(o.shape[0], -1), w["w_o"],
                   preferred_element_type=f32)


def latent_query(cfg: LongCatConfig, w, q_nope, q_rope, width: int):
    """The decode form's query ``[S, H, width]``: the key half of
    ``W_kvb`` absorbed into ``q_nope``, then ``q_rope``, then zeros up to
    the cache rows' ``width``, so ONE product runs over the rows as they
    lie."""
    dt = cfg.dtype
    q_lat = jnp.einsum("shd,chd->shc", q_nope, _kvb(cfg, w)[0],
                       preferred_element_type=jnp.float32).astype(dt)
    pad = width - cfg.cache_width
    return jnp.concatenate(
        [q_lat, q_rope] + ([jnp.zeros(q_rope.shape[:2] + (pad,), dt)]
                           if pad else []), axis=-1)


def latent_output(cfg: LongCatConfig, w, o_lat, gate=None):
    """The decode form's tail: the attended latents ``o_lat`` [S, H,
    rkv] through the value half of ``W_kvb``, :func:`head_gate`'s
    ``gate`` and ``W_o``: [S, D] float32."""
    f32, dt = jnp.float32, cfg.dtype
    o = _gated(jnp.einsum("shc,chd->shd", o_lat.astype(dt), _kvb(cfg, w)[1],
                          preferred_element_type=f32), gate).astype(dt)
    return jnp.dot(o.reshape(o.shape[0], -1), w["w_o"],
                   preferred_element_type=f32)


def mla_latent(cfg: LongCatConfig, w, q_nope, q_rope, view, pos,
               gate=None):
    """The decode form: one query a slot, ``q_nope``/``q_rope`` [S, H,
    .], against each slot's cache rows ``view`` [S, T, >= rkv + dr] as
    they lie (the query is padded with zeros to their width); positions
    ``<= pos`` [S] are live. The key half of ``W_kvb`` is absorbed into
    the query and the value half into the output, so both products run
    over the cache rows. Returns [S, D] float32."""
    f32 = jnp.float32
    q_cat = latent_query(cfg, w, q_nope, q_rope, view.shape[-1])
    s = jnp.einsum("shc,stc->sht", q_cat, view,
                   preferred_element_type=f32) / cfg.softmax_divisor
    live = jnp.arange(view.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, _NEG_INF), axis=-1)
    o_lat = jnp.einsum("sht,stc->shc", p.astype(cfg.dtype),
                       view[..., :cfg.kv_lora_rank],
                       preferred_element_type=f32)
    return latent_output(cfg, w, o_lat, gate)


def expert_layer(cfg: LongCatConfig, blk, u, valid=None, identity=True):
    """``(m [T, D] float32, counts)``: this chip's part of ``MoE(u)``."""
    idx, gates = route_topk(u, blk["router"], blk["router_bias"],
                            cfg.moe_topk, cfg.routed_scaling_factor)
    return held_expert_layer(u, idx, gates, blk["experts"],
                             cfg.total_routed_experts, cfg.expert_offset,
                             identity=identity, valid=valid)


def _ffn(w, x):
    return swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def block_apply(cfg: LongCatConfig, blk, h, attend, valid=None):
    """One double block on the residual stream ``h`` [T, D] (float32).
    ``attend(j, w, x)`` runs MLA sublayer ``j`` of this block on the
    normed input ``x`` and returns its output [T, D]."""
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    m0, m1 = blk["mla"]
    f0, f1 = blk["ffn"]
    h = h + attend(0, m0, rmsnorm(h, m0["norm"], eps, dt))
    u = rmsnorm(h, f0["norm"], eps, dt)
    m, counts = expert_layer(cfg, blk, u, valid)
    h = h + _ffn(f0, u)
    h = h + attend(1, m1, rmsnorm(h, m1["norm"], eps, dt))
    h = h + _ffn(f1, rmsnorm(h, f1["norm"], eps, dt)) + m
    return h, counts


def _logits(cfg, params, h):
    x = rmsnorm(h, params["final_norm"], cfg.rms_norm_eps, cfg.dtype)
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def forward(cfg: LongCatConfig, params, tokens) -> jax.Array:
    """Full causal forward pass of ONE sequence ``tokens`` [T], no
    cache: logits [T, V] in float32."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def attend(j, w, x):
        q_nope, q_rope, rows = mla_project(cfg, w, x, pos)
        return mla_expanded(cfg, w, q_nope, q_rope, rows, mask)

    for blk in params["blocks"]:
        h, _ = block_apply(cfg, blk, h, attend)
    return _logits(cfg, params, h)


# -- paged programs ---------------------------------------------------------------
# What every model with a latent pool shares (this one and
# ``models/deepseek_v3.py``): where a step and a chunk write, one MLA
# sublayer of each against the pool, the copy-on-write, and the builder
# of the engine's seam. A model's own programs are the walk over its
# layers.
def _view(pool, sub: int, tables, t: int):
    """Sublayer ``sub``'s blocks named by ``tables`` ([S, M] or [M]),
    gathered from the pool seen as ``[subs * N, Bs, W]`` (the sublayer
    inside the index: no copy of a sublayer's pool), cut to ``t``."""
    n_sub, N, Bs, W = pool.shape
    blocks = jnp.take(pool.reshape(n_sub * N, Bs, W), sub * N + tables,
                      axis=0, mode="clip")
    rows = blocks.reshape(tables.shape[:-1] + (tables.shape[-1] * Bs, W))
    return rows[..., :t, :]


def step_rows(pool, block_tables, pos, active):
    """Where a one-token step writes and what it attends over: ``(block
    [S], offset [S], lengths [S])``. A live slot writes its row at
    ``(block_tables[s, pos // Bs], pos % Bs)``, a dead lane parks its in
    scratch block 0 and attends over nothing."""
    Bs = pool.shape[2]
    blk_ix = jnp.take_along_axis(block_tables, (pos // Bs)[:, None],
                                 axis=1)[:, 0]
    return (jnp.where(active, blk_ix, 0), jnp.where(active, pos % Bs, 0),
            jnp.where(active, pos + 1, 0))


def step_attend(cfg, w, x, pool, sub: int, block_tables, pos, rows,
                t_logical: int, paged_attention=None):
    """MLA sublayer ``sub`` of a one-token step on the normed ``x``
    [S, D]: each slot's cache row written at ``rows``
    (:func:`step_rows`), then attention IN THE LATENT. Returns ``(pool,
    out [S, D] float32)``. ``paged_attention``
    (``ops.paged_attention.paged_mq_attention``, where a model's
    ``serving_programs`` finds it applies) reads each slot's live blocks
    out of the pool in place of the gathered view: the same latent
    query, keys the whole rows, values their first ``rkv`` columns."""
    n_sub, N, Bs, W = pool.shape
    write_blk, write_off, lengths = rows
    q_nope, q_rope, row = mla_project(cfg, w, x, pos, W)
    gate = head_gate(cfg, w, x)
    pool = pool.at[sub, write_blk, write_off].set(row)
    if paged_attention is not None:
        o_lat = paged_attention(
            latent_query(cfg, w, q_nope, q_rope, W),
            pool.reshape(n_sub * N, Bs, W), None,
            sub * N + block_tables, lengths,
            scale=1.0 / cfg.softmax_divisor, wv=cfg.kv_lora_rank)
        return pool, latent_output(cfg, w, o_lat, gate)
    # the barrier holds the view as ONE array between its two readers
    # (scores, values): with weights and pool filling the chip the TPU
    # compiler otherwise rematerializes the gather, once a reader (14
    # gathers of 377 MB a step for 8)
    view = jax.lax.optimization_barrier(
        _view(pool, sub, block_tables, t_logical))
    return pool, mla_latent(cfg, w, q_nope, q_rope, view, pos, gate)


def chunk_rows(pool, block_tables, slot, chunk: int, offset, length,
               t_logical: int):
    """Where a prefill chunk of ONE slot writes and what it sees:
    ``(bt_row [M], pos_ix [C], valid [C], block [C], offset [C], mask
    [C, T])``; ``slot``/``offset``/``length`` traced, pad rows routed to
    scratch."""
    Bs = pool.shape[2]
    M = block_tables.shape[1]
    bt_row = jax.lax.dynamic_index_in_dim(block_tables, slot, 0,
                                          keepdims=False)
    pos_ix = offset + jnp.arange(chunk)
    valid = jnp.arange(chunk) < length
    blk_ix = jnp.where(
        valid, jnp.take(bt_row, jnp.clip(pos_ix // Bs, 0, M - 1)), 0)
    off = jnp.where(valid, pos_ix % Bs, 0)
    mask = jnp.arange(t_logical)[None, :] <= pos_ix[:, None]
    return bt_row, pos_ix, valid, blk_ix, off, mask


def chunk_attend(cfg, w, x, pool, sub: int, rows, t_logical: int):
    """MLA sublayer ``sub`` of a prefill chunk on the normed ``x``
    [C, D]: the chunk's cache rows written at ``rows``
    (:func:`chunk_rows`), then its queries attend the slot's rows
    EXPANDED to per-head keys and values. Returns ``(pool, out [C, D]
    float32)``."""
    bt_row, pos_ix, _, blk_ix, off, mask = rows
    q_nope, q_rope, new = mla_project(cfg, w, x, pos_ix, pool.shape[-1])
    pool = pool.at[sub, blk_ix, off].set(new)
    view = _view(pool, sub, bt_row, t_logical)
    return pool, mla_expanded(cfg, w, q_nope, q_rope, view, mask,
                              head_gate(cfg, w, x))


def greedy_next(cfg, params, h, tok, pos, active):
    """A step's tail: ``(next_tok, pos)``, the greedy choice over the
    vocabulary held here, dead lanes at token 0 and where they were."""
    nxt = jnp.argmax(_logits(cfg, params, h), axis=-1).astype(tok.dtype)
    nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
    return nxt, jnp.where(active, pos + 1, pos)


def last_logits(cfg, params, h, length):
    """A chunk's tail: the logits [V] of its last valid row."""
    last = jnp.take(h, length - 1, axis=0)
    return _logits(cfg, params, last[None])[0]


def decode_step_paged(cfg: LongCatConfig, params, pool, counters,
                      block_tables, tok, pos, active, t_logical: int,
                      paged_attention=None):
    """One fused token step over S slots against the paged latent pool
    ``[subs, N + 1, Bs, pool_width]`` (block 0 = scratch), by the
    engine's contract (``models.transformer.decode_step_paged``;
    :func:`step_rows`, :func:`step_attend`). ``counters`` accumulates
    the routing counts of live slots. Returns ``(pool, counters,
    next_tok, pos)``."""
    rows = step_rows(pool, block_tables, pos, active)
    h = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    for b, blk in enumerate(params["blocks"]):
        def attend(j, w, x, b=b):
            nonlocal pool
            pool, out = step_attend(cfg, w, x, pool, 2 * b + j,
                                    block_tables, pos, rows, t_logical,
                                    paged_attention)
            return out

        h, counts = block_apply(cfg, blk, h, attend, valid=active)
        counters = counters.at[b].add(counts)
    return (pool, counters) + greedy_next(cfg, params, h, tok, pos, active)


def prefill_chunk_paged(cfg: LongCatConfig, params, pool, counters,
                        block_tables, slot, tokens, offset, length,
                        t_logical: int):
    """Incremental prefill of one fixed-size chunk of ONE slot into the
    paged latent pool (``models.transformer.prefill_chunk_paged``'s
    contract; :func:`chunk_rows`, :func:`chunk_attend`). Returns
    ``(pool, counters, last_logits [V])``."""
    rows = chunk_rows(pool, block_tables, slot, tokens.shape[0], offset,
                      length, t_logical)
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    for b, blk in enumerate(params["blocks"]):
        def attend(j, w, x, b=b):
            nonlocal pool
            pool, out = chunk_attend(cfg, w, x, pool, 2 * b + j, rows,
                                     t_logical)
            return out

        h, counts = block_apply(cfg, blk, h, attend, valid=rows[2])
        counters = counters.at[b].add(counts)
    return pool, counters, last_logits(cfg, params, h, length)


def cow_block_copy(pool, counters, src, dst):
    """Copy-on-write of one block of the latent pool (every sublayer)."""
    return pool.at[:, dst].set(pool[:, src]), counters


def latent_pool_programs(cfg, spec, model: str, step, chunk,
                         counter_shape: tuple, counters,
                         slot_pools: tuple = (), lacking=None):
    """The engine's seam (``serving/programs.py``) for a model whose
    per-TOKEN cache is one latent pool ``[cfg.n_sublayers, N + 1, Bs,
    cfg.pool_width]``. The pools, in this order: pool 0, the latent pool
    (the engine takes block size and block shape from pool 0, so it
    stays block-shaped); then ``slot_pools``, ``(shape, dtype)`` each
    with ``slots`` as the second axis: what a model keeps per SEQUENCE
    and not per token (a recurrent state), rows that a slot's first
    chunk resets and its later chunks and steps carry; last, one small
    counters array. ``step`` and ``chunk`` (the model's
    ``decode_step_paged`` / ``prefill_chunk_paged``) are jitted over
    them in that order with every pool but the counters donated, and
    the copy-on-write over the latent pool. The programs' names in a
    profile are ``jit_<model>_decode_step``,
    ``jit_<model>_prefill_chunk`` and ``jit_<model>_cow_block``. What
    such a model lacks is refused here, by name, never run wrong:
    ``lacking`` adds a model's own refusals (feature -> why)."""
    from ..serving.programs import ServingPrograms, refuse

    who = f"DecodeEngine {spec.name!r} ({model})"
    refuse(who, spec, **{
        "kv_quant": "no int8 latent pool",
        "param_quant": "no int8 parameter pin",
        "decode_tp": "no tensor-parallel decode programs",
        "spec_k": "no verify step",
        "prefill_sp": "no sequence-parallel prefill", **(lacking or {})})
    if spec.cache_len > cfg.max_seq:
        Log.fatal(f"{who}: max_prompt + max_new {spec.cache_len} "
                  f"exceeds max_position_embeddings {cfg.max_seq}")
    T = spec.cache_len
    n_state = len(slot_pools)
    donate = tuple(range(1, 2 + n_state)) if spec.donate else ()
    # the one-token step reads the live blocks in place where a
    # block is whole tiles on a TPU; the chunk keeps the view
    attend = paged_kernel.step_attention(cfg.dtype, spec.block_size,
                                         cfg.pool_width)

    # ``rest``: the slot pools, the counters, then the program's data
    def decode_step(params, pool, *rest):
        return step(cfg, params, pool, *rest, T, paged_attention=attend)

    def prefill_chunk(params, pool, *rest):
        return chunk(cfg, params, pool, *rest, T)

    def cow_block(pool, counters, src, dst):
        return cow_block_copy(pool, counters, src, dst)

    # a function's name is its program's in a profile (jit_<name>)
    for fn in (decode_step, prefill_chunk, cow_block):
        fn.__name__ = fn.__qualname__ = f"{model}_{fn.__name__}"
    pool_shape = (cfg.n_sublayers, spec.pool_blocks + 1, spec.block_size,
                  cfg.pool_width)
    return ServingPrograms(
        pools=((pool_shape, jnp.dtype(cfg.dtype)),
               *((tuple(shape), jnp.dtype(dtype))
                 for shape, dtype in slot_pools),
               (counter_shape, jnp.dtype(jnp.float32))),
        bytes_per_block=(cfg.n_sublayers * spec.block_size * cfg.pool_width
                         * jnp.dtype(cfg.dtype).itemsize),
        bytes_per_slot=sum(
            int(np.prod(shape)) // spec.slots * jnp.dtype(dtype).itemsize
            for shape, dtype in slot_pools),
        step=jax.jit(decode_step, donate_argnums=donate),
        chunk=jax.jit(prefill_chunk, donate_argnums=donate),
        cow=(jax.jit(cow_block, donate_argnums=(0,) if spec.donate else ())
             if spec.prefix else None),
        # pin: the default, the snapshot itself (a serve-only model's
        # weights never move and nothing donates them)
        counter_pool=1 + n_state, counters=counters)


def routing_summary(cfg: LongCatConfig, counts: np.ndarray) -> dict:
    """``eng.stats()``'s routing keys from the accumulated counters
    ``[blocks, 4 + held experts]`` (layout of
    ``ops.moe.held_expert_layer``): per token and expert layer, picks of
    FFN experts, of identity experts, of held experts, and the held
    experts' load max over mean (their summed loads over all layers)."""
    c = np.asarray(counts, np.float64)
    layer_tokens = float(c[:, 0].sum())
    if layer_tokens <= 0:
        return {"moe_layer_tokens": 0.0}
    load = c[:, COUNT_SCALARS:].sum(0)
    return {"moe_layer_tokens": layer_tokens,
            "moe_ffn_picks_per_token": float(c[:, 1].sum()) / layer_tokens,
            "moe_identity_picks_per_token":
                float(c[:, 2].sum()) / layer_tokens,
            "moe_held_pairs": float(c[:, 3].sum()),
            "moe_held_pairs_per_token": float(c[:, 3].sum()) / layer_tokens,
            "moe_held_load_max_over_mean":
                float(load.max() / load.mean()) if load.mean() > 0 else 0.0}


class LongCatLM:
    """Serve-only LongCat-Flash share: weights drawn on the device from
    ``config.seed``; exposes the snapshot contract (``version`` never
    moves; ``snapshot_params`` hands the weights out WITHOUT a copy,
    nothing ever donates them) and the engine's seam."""

    def __init__(self, config: LongCatConfig) -> None:
        if config.expert_offset + config.n_routed_experts \
                > config.total_routed_experts:
            Log.fatal("LongCatLM: the held experts "
                      f"[{config.expert_offset}, +{config.n_routed_experts})"
                      f" lie outside the {config.total_routed_experts} "
                      "the router addresses")
        self.config = config
        self.version = 0
        self.params = init_params(config)

    def snapshot_params(self) -> Tuple[Dict[str, Any], int]:
        return self.params, self.version

    def logits(self, tokens: np.ndarray) -> jax.Array:
        return forward(self.config, self.params,
                       jnp.asarray(tokens, jnp.int32))

    def serving_programs(self, spec):
        """The engine's seam: one latent pool and one small counters
        array, the decode step (latent form), the prefill chunk
        (expanded form) and the copy-on-write
        (:func:`latent_pool_programs`)."""
        cfg = self.config
        return latent_pool_programs(
            cfg, spec, "longcat", decode_step_paged, prefill_chunk_paged,
            (cfg.num_layers, COUNT_SCALARS + cfg.n_routed_experts),
            lambda delta: routing_summary(cfg, delta))
