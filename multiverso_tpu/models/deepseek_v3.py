"""A DeepSeek-V3-architecture language model on the serving path, one
chip's share (first served: dots.vlm1.inst's language model, whose
``config.json`` carries DeepSeek-V3's keys letter for letter).

The layers come in a PATTERN: ``first_k_dense_replace`` leading layers
with a dense SwiGLU FFN, expert layers after. With ``N`` an RMSNorm with
its own gain at each use, layer ``l`` is::

    h' = h  + MLA(N(h))
    y  = h' + F_l(N(h'))        F_l = FFN (l < first_k_dense_replace)
                                      MoE  (after)
    MoE(u) = sum_k g_k E_k(u) + E_shared(u)

* **MLA** is ``models/longcat.py``'s, the one copy for both models
  (:func:`longcat.mla_project`, the expanded form in prefill, the latent
  form in the decode step, the same ``[c, k_rope]`` cache row in a pool
  of 640-wide rows). What differs comes from this configuration: no
  ``mla_scale_*`` factors, YaRN rotary frequencies
  (``rope_scaling``), and a softmax scale of ``(dn + dr)^-0.5 m^2``
  with ``m = 0.1 mscale_all_dim ln(factor) + 1``.
* **The router** (:func:`ops.moe.route_group_limited`): sigmoid scores,
  a token limited to ``topk_group`` of ``n_group`` groups of experts,
  ``num_experts_per_tok`` picks inside them by score plus
  ``e_score_correction_bias``, gates normalised to sum 1 and scaled by
  ``routed_scaling_factor``. No token dropped, no capacity.
* **The share.** This chip holds ``n_routed_experts`` of the
  ``published.n_routed_experts`` routed experts from ``expert_offset``
  (:func:`ops.moe.held_expert_layer`: dense over the held experts under
  a gate mask, picks of absent experts add nothing); the router keeps
  every output, group and pick. The shared expert, attention and the
  dense FFN are whole on every chip; when shares are summed the shared
  expert counts once.

Not here: the vision tower (requests are token ids) and the
multi-token-prediction module (``num_nextn_predict_layers``; a drafter
needs a verify step over the latent pool, refused by name below).
Weights are drawn ON THE DEVICE leaf by leaf from one law
(:func:`init_params`); the model is serve-only and its snapshot is the
weights themselves.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops.moe import (COUNT_SCALARS, held_expert_layer, route_group_limited,
                       swiglu)
from . import longcat
from .longcat import rmsnorm


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config(longcat.LatentCacheSizes):
    vocab_size: int = 129280           # rows held here (a slice)
    hidden_size: int = 7168
    intermediate_size: int = 18432     # the leading dense layers' FFN
    moe_intermediate_size: int = 2048  # a routed or shared expert
    num_hidden_layers: int = 61        # layers held here
    first_k_dense_replace: int = 3     # of them, leading dense layers
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 256        # routed experts HELD here
    total_routed_experts: int = 256    # routed experts the router addresses
    expert_offset: int = 0             # first held expert's router output
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN: factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim (required: plain rotary is refused)
    rope_scaling: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.bfloat16
    seed: int = 0

    # what longcat.py's MLA code asks of a configuration
    mla_scale_q_lora = False
    mla_scale_kv_lora = False
    mla_head_gate = False

    @property
    def n_sublayers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_divisor(self) -> float:
        """Scores are divided by ``sqrt(dn + dr) / m^2``: YaRN's
        ``mscale_all_dim`` term, squared, on the softmax scale."""
        y = self.rope_scaling
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        return math.sqrt(self.qk_nope_head_dim
                         + self.qk_rope_head_dim) / (m * m)

    @property
    def home_groups(self) -> slice:
        """The router's groups that the held experts lie in."""
        size = self.total_routed_experts // self.n_group
        return slice(self.expert_offset // size,
                     (self.expert_offset + self.n_routed_experts - 1)
                     // size + 1)


def config_from_dict(cfg: dict, seed: int) -> DeepSeekV3Config:
    return longcat.share_config(DeepSeekV3Config, cfg, seed)


# -- the weight law -----------------------------------------------------------
# As longcat.py's: one key per (layer, leaf) from the seed, and per routed
# expert by its ROUTER OUTPUT index; float32 normal on the device times the
# leaf's std, rounded to the configuration's dtype; router and bias stay
# float32. The reference keeps its own copy of this law.
_LEAVES = ("embed", "head", "w_qa", "w_qb", "w_kva", "w_kvb", "w_o",
           "w_gate", "w_up", "w_down", "router", "router_bias",
           "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
# std is 1/sqrt(fan_in) but: W_qb 0.8 of it (queries of 0.8 an element
# against unit keys over 192 dimensions, times 192^-0.5 m^2 = 0.135: a
# score std near 1.5, neither flat nor one-hot); the router at it (logits
# of std 1: sigmoid scores spread over 0.1-0.9, the picks' near 0.85-0.95);
# the bias 0.02 an output, NOT zero: the scores around the 8th pick lie
# ~0.007 apart, so it moves picks, and a gate taken from s + b would show;
# W_o 4 times it: a head's output is a softmax average over ~100 effective
# keys of unit values, 0.1 an element, so at 1/sqrt(fan_in) attention adds
# a sixth of what an FFN adds to the stream and next to nothing in the
# logits depends on the cache; at 4 times it adds 0.4, an FFN 0.6.
# A routed expert is drawn like the shared one: normalised sigmoid gates
# are 2.5 / 8 = 0.31 whatever a pick's rank (a softmax's last gate is
# small), so an 8th-against-9th pick that flips on bfloat16 rounding swaps
# a whole expert and the WIDEST gap of a served token reads rounding and a
# fault alike; the cell's comparison therefore reads the MEAN gap
# (benchmarks/drivers/serve_closed_routed.py; PERF.md, PR 32), and the law
# leaves the experts their weight in the stream
_QB_GAIN = 0.8
_BIAS_STD = 0.02
_WO_GAIN = 4.0


def _leaf_key(seed: int, layer: int, leaf: str):
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
                             layer + 1)
    return jax.random.fold_in(key, _LEAVES.index(leaf))


def drawers(dtype):
    """``(draw(key, shape, std, dtype), draw_experts(keys, shape, std))``:
    one leaf, and a stack of leaves a key each in ``dtype``, each one
    jitted draw on the default device."""
    return (jax.jit(longcat._draw, static_argnums=(1, 2, 3)),
            jax.jit(lambda keys, shape, std: jax.vmap(
                lambda k: longcat._draw(k, shape, std, dtype))(keys),
                static_argnums=(1, 2)))


def draw_ffn_layer(cfg, draws, key, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s FFN leaves, ``key(leaf)`` the leaf's key: a
    dense SwiGLU (``ffn``) in the ``first_k_dense_replace`` leading
    layers; after them the router and its bias (float32), the held
    routed experts (a key an expert, by its ROUTER OUTPUT index) and the
    shared expert. std ``1/sqrt(fan_in)``, the bias ``_BIAS_STD``."""
    draw, draw_experts = draws
    D, Fe, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype

    def ffn(names, width):
        return {"w_gate": draw(key(names[0]), (D, width), D ** -0.5, dt),
                "w_up": draw(key(names[1]), (D, width), D ** -0.5, dt),
                "w_down": draw(key(names[2]), (width, D), width ** -0.5, dt)}

    if layer < cfg.first_k_dense_replace:
        return {"ffn": ffn(("w_gate", "w_up", "w_down"),
                           cfg.intermediate_size)}
    ids = cfg.expert_offset + jnp.arange(cfg.n_routed_experts)
    keys = lambda leaf: jax.vmap(
        lambda e: jax.random.fold_in(key(leaf), e))(ids)
    E = cfg.total_routed_experts
    return {
        "router": draw(key("router"), (D, E), D ** -0.5, jnp.float32),
        "router_bias": draw(key("router_bias"), (E,), _BIAS_STD,
                            jnp.float32),
        "experts": {
            "w_gate": draw_experts(keys("e_gate"), (D, Fe), D ** -0.5),
            "w_up": draw_experts(keys("e_up"), (D, Fe), D ** -0.5),
            "w_down": draw_experts(keys("e_down"), (Fe, D), Fe ** -0.5)},
        "shared": ffn(("s_gate", "s_up", "s_down"),
                      cfg.n_shared_experts * Fe)}


def init_params(cfg: DeepSeekV3Config) -> Dict[str, Any]:
    """The share's weights, each leaf one jitted draw on the default
    device (never the whole tree at once, never on the host)."""
    D, dt = cfg.hidden_size, cfg.dtype
    draws = drawers(dt)
    draw = draws[0]
    ones = lambda n: jnp.ones((n,), jnp.float32)

    layers = []
    for l in range(cfg.num_hidden_layers):
        k = lambda leaf: _leaf_key(cfg.seed, l, leaf)
        layers.append({
            "mla": longcat.draw_mla(cfg, draw, k, _QB_GAIN, _WO_GAIN),
            "ffn_norm": ones(D), **draw_ffn_layer(cfg, draws, k, l)})
    return {
        # unit variance an element, as longcat.py's (an embedding of norm
        # 1 is swamped by the first sublayer's output)
        "embed": draw(_leaf_key(cfg.seed, -1, "embed"),
                      (cfg.vocab_size, D), 1.0, dt),
        "head": draw(_leaf_key(cfg.seed, -1, "head"),
                     (D, cfg.vocab_size), D ** -0.5, dt),
        "final_norm": ones(D),
        "layers": layers}


# -- a layer ------------------------------------------------------------------
def expert_layer(cfg: DeepSeekV3Config, layer, u, valid=None):
    """``(y [T, D] float32, counts)``: this chip's part of ``MoE(u)``, the
    held experts' and the shared expert's, which every chip computes
    alike: shares are summed with it counted once. ``counts``
    is :func:`ops.moe.held_expert_layer`'s, then the number of ``valid``
    tokens whose kept groups include one the held experts lie in."""
    idx, gates, kept = route_group_limited(
        u, layer["router"], layer["router_bias"], cfg.num_experts_per_tok,
        cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor)
    y, counts = held_expert_layer(u, idx, gates, layer["experts"],
                                  cfg.total_routed_experts,
                                  cfg.expert_offset, identity=False,
                                  valid=valid)
    y = y + swiglu(u, **layer["shared"])
    home = jnp.any(kept[:, cfg.home_groups], axis=-1)
    if valid is not None:
        home = home & valid
    return y, jnp.concatenate(
        [counts, jnp.sum(home.astype(jnp.float32))[None]])


def layer_apply(cfg: DeepSeekV3Config, layer, h, attend, valid=None):
    """One layer on the residual stream ``h`` [T, D] (float32).
    ``attend(w, x)`` runs the layer's MLA on the normed input ``x`` and
    returns its output [T, D]. Returns ``(h, counts)``, ``counts`` None
    for a dense layer."""
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    w = layer["mla"]
    h = h + attend(w, rmsnorm(h, w["norm"], eps, dt))
    u = rmsnorm(h, layer["ffn_norm"], eps, dt)
    if "ffn" in layer:
        return h + swiglu(u, **layer["ffn"]), None
    y, counts = expert_layer(cfg, layer, u, valid)
    return h + y, counts


def forward(cfg: DeepSeekV3Config, params, tokens) -> jax.Array:
    """Full causal forward pass of ONE sequence ``tokens`` [T], no
    cache: logits [T, V] in float32."""
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def attend(w, x):
        q_nope, q_rope, rows = longcat.mla_project(cfg, w, x, pos)
        return longcat.mla_expanded(cfg, w, q_nope, q_rope, rows, mask)

    for layer in params["layers"]:
        h, _ = layer_apply(cfg, layer, h, attend)
    return longcat._logits(cfg, params, h)


# -- paged programs: the walk over the layers (the rest is longcat.py's) --------
def _walk(cfg, params, h, pool, counters, attend, valid):
    """Every layer over ``h``; ``attend(w, x, pool, l) -> (pool, out)``.
    An expert layer's counts go to its row of ``counters``."""
    for l, layer in enumerate(params["layers"]):
        def attend_l(w, x, l=l):
            nonlocal pool
            pool, out = attend(w, x, pool, l)
            return out

        h, counts = layer_apply(cfg, layer, h, attend_l, valid=valid)
        if counts is not None:
            counters = counters.at[l - cfg.first_k_dense_replace].add(counts)
    return h, pool, counters


def decode_step_paged(cfg: DeepSeekV3Config, params, pool, counters,
                      block_tables, tok, pos, active, t_logical: int,
                      paged_attention=None):
    """One fused token step over S slots against the paged latent pool
    ``[layers, N + 1, Bs, pool_width]`` (``longcat.decode_step_paged``'s
    contract). Returns ``(pool, counters, next_tok, pos)``."""
    rows = longcat.step_rows(pool, block_tables, pos, active)
    h = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)
    h, pool, counters = _walk(
        cfg, params, h, pool, counters,
        lambda w, x, pool, l: longcat.step_attend(
            cfg, w, x, pool, l, block_tables, pos, rows, t_logical,
            paged_attention), active)
    return (pool, counters) + longcat.greedy_next(cfg, params, h, tok, pos,
                                                  active)


def prefill_chunk_paged(cfg: DeepSeekV3Config, params, pool, counters,
                        block_tables, slot, tokens, offset, length,
                        t_logical: int):
    """Incremental prefill of one fixed-size chunk of ONE slot
    (``longcat.prefill_chunk_paged``'s contract). Returns ``(pool,
    counters, last_logits [V])``."""
    rows = longcat.chunk_rows(pool, block_tables, slot, tokens.shape[0],
                              offset, length, t_logical)
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    h, pool, counters = _walk(
        cfg, params, h, pool, counters,
        lambda w, x, pool, l: longcat.chunk_attend(cfg, w, x, pool, l, rows,
                                                   t_logical), rows[2])
    return pool, counters, longcat.last_logits(cfg, params, h, length)


def routing_summary(cfg: DeepSeekV3Config, counts: np.ndarray) -> dict:
    """``eng.stats()``'s routing keys from the accumulated counters
    ``[expert layers, 4 + held experts + 1]``: ``longcat.py``'s, and the
    share of (token, expert layer) pairs whose kept groups include one
    the held experts lie in."""
    c = np.asarray(counts, np.float64)
    out = longcat.routing_summary(cfg, c[:, :-1])
    if out["moe_layer_tokens"] > 0:
        out["moe_home_group_share"] = \
            float(c[:, -1].sum()) / out["moe_layer_tokens"]
    return out


def check_share(who: str, c) -> None:
    """What a group-limited share has to satisfy whatever the model: the
    held experts inside the router's outputs, groups that hold the
    picks, the dense layers inside the layers held."""
    if c.expert_offset + c.n_routed_experts > c.total_routed_experts:
        Log.fatal(f"{who}: the held experts [{c.expert_offset}, "
                  f"+{c.n_routed_experts}) lie outside the "
                  f"{c.total_routed_experts} the router addresses")
    if c.total_routed_experts % c.n_group \
            or not 0 < c.topk_group <= c.n_group \
            or c.total_routed_experts // c.n_group < 2 \
            or c.num_experts_per_tok > c.topk_group \
            * (c.total_routed_experts // c.n_group):
        Log.fatal(f"{who}: {c.total_routed_experts} experts do not "
                  f"make {c.n_group} groups of two or more of which "
                  f"{c.topk_group} hold {c.num_experts_per_tok} picks")
    if not 0 <= c.first_k_dense_replace <= c.num_hidden_layers:
        Log.fatal(f"{who}: first_k_dense_replace "
                  f"{c.first_k_dense_replace} outside the "
                  f"{c.num_hidden_layers} layers")


class DeepSeekV3LM:
    """Serve-only share of a DeepSeek-V3-architecture model: weights
    drawn on the device from ``config.seed``; the snapshot contract and
    the engine's seam as :class:`longcat.LongCatLM` has them."""

    def __init__(self, config: DeepSeekV3Config) -> None:
        c = config
        who = "DeepSeekV3LM"
        check_share(who, c)
        y = c.rope_scaling
        if y is None or y.get("type", "yarn") != "yarn" \
                or y["factor"] <= 1 or y["mscale"] != y["mscale_all_dim"]:
            Log.fatal(f"{who}: rope_scaling is not supported but as YaRN "
                      "with a factor over 1 and mscale == mscale_all_dim "
                      f"(the rotation itself unscaled), got {y}")
        if not c.norm_topk_prob:
            Log.fatal(f"{who}: norm_topk_prob false is not supported: the "
                      "picks' gates are normalised to sum 1")
        if c.n_shared_experts < 1:
            Log.fatal(f"{who}: n_shared_experts {c.n_shared_experts} is "
                      "not supported: an expert layer has a shared expert")
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
        model's walk; programs ``jit_dsv3_decode_step``,
        ``jit_dsv3_prefill_chunk``, ``jit_dsv3_cow_block``."""
        cfg = self.config
        return longcat.latent_pool_programs(
            cfg, spec, "dsv3", decode_step_paged, prefill_chunk_paged,
            (max(cfg.n_expert_layers, 1),
             COUNT_SCALARS + cfg.n_routed_experts + 1),
            lambda delta: routing_summary(cfg, delta))
