"""A ``bailing_hybrid`` language model on the serving path, one chip's
share (first served: Ling-3.0-flash): TWO kinds of attention layer over
two kinds of cache.

Layer ``i`` is a latent-attention (MLA) layer where ``(i + 1) %
layer_group_size == 0`` and a Kimi-Delta-Attention (KDA) layer
otherwise; the first ``first_k_dense_replace`` layers carry a dense
SwiGLU FFN, the rest a mixture of experts with a shared expert. With
``N`` an RMSNorm with its own gain at each use::

    h' = h  + A_i(N(h))           A_i = KDA or MLA
    y  = h' + F_i(N(h'))          F_i = FFN or MoE + E_shared

* **KDA** (``ops/kda.py``; arXiv:2510.26692). ``[q~, k~, v~] = x
  W_qkv``; each passes a depthwise causal convolution of
  ``short_conv_kernel_size`` taps, then SiLU; per head ``q = q' / |q'|
  dk^-0.5``, ``k = k' / |k'|``; the per-channel decay ``log a = lower *
  sigmoid(exp(A_log[h]) (x W_f + dt_bias))`` with ``lower =
  kda_lower_bound`` (so ``a`` in ``(e^lower, 1)``); the write strength
  ``b = sigmoid(x w_beta)``; the gated delta rule over a float32 state
  ``[dk, dv]`` a head; ``y = [N_head(o) * sigmoid(x W_g)] W_o``. What a
  SEQUENCE holds is that state and the last ``taps - 1`` rows of
  ``[q~, k~, v~]`` (the conv tail): fixed bytes a slot whatever its
  length, in two SLOT pools (``serving/programs.py``).
* **MLA** is ``models/longcat.py``'s, the one copy for three models,
  against its paged latent pool, with this configuration's settings: no
  query latent (``q_lora_rank`` None), plain rotary frequencies, and a
  head-wise sigmoid gate on the attention output (``mla_head_gate``).
* **The experts** are ``models/deepseek_v3.py``'s layer
  (:func:`deepseek_v3.expert_layer`: ``ops.moe.route_group_limited``,
  ``held_expert_layer`` over the held share, the shared expert whole).

A prefill chunk starts from zero where it starts a prompt (``offset ==
0``) and from the slot's rows otherwise; a step leaves an inactive
slot's rows bit-identical. Neither a prefix hit nor a speculative
window can be served: blocks restore no state and a state cannot be
rolled back, so both are refused by name (:meth:`LingLM.serving_programs`).

Not here: the multi-token-prediction module and the SwiGLU clamp
(``expert_swiglu_limit_list``; refused unless 0 for every layer held).
Weights are drawn ON THE DEVICE leaf by leaf from one law
(:func:`init_params`); the model is serve-only and its snapshot is the
weights themselves.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..log import Log
from ..ops import kda
from ..ops.moe import COUNT_SCALARS, swiglu
from . import deepseek_v3, longcat
from .longcat import rmsnorm

# columns of the counters' last row (the KDA layers' own)
_DECAY_SUM, _KDA_TOKENS, _RESETS = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class LingConfig(longcat.LatentCacheSizes):
    vocab_size: int = 157184           # rows held here (a slice)
    hidden_size: int = 2560
    intermediate_size: int = 6144      # the leading dense layers' FFN
    moe_intermediate_size: int = 768   # a routed or shared expert
    num_hidden_layers: int = 42        # layers held here
    first_k_dense_replace: int = 2     # of them, leading dense layers
    layer_group_size: int = 6          # the last of each group is MLA
    num_attention_heads: int = 32      # both attention kinds
    head_dim: int = 128                # KDA's dk = dv
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 512        # routed experts HELD here
    total_routed_experts: int = 512    # routed experts the router addresses
    expert_offset: int = 0             # first held expert's router output
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6e6
    dtype: Any = jnp.bfloat16
    seed: int = 0

    # what longcat.py's MLA code asks of a configuration
    mla_scale_q_lora = False
    mla_scale_kv_lora = False
    mla_head_gate = True
    rope_scaling = None

    @property
    def softmax_divisor(self) -> float:
        return math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    @property
    def n_sublayers(self) -> int:
        """Layers with a per-token cache row: the latent pool's depth."""
        return sum(self.is_mla(i) for i in range(self.num_hidden_layers))

    @property
    def n_kda_layers(self) -> int:
        return self.num_hidden_layers - self.n_sublayers

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def conv_width(self) -> int:
        """Channels of ``[q~, k~, v~]``: what the convolution runs over."""
        return 3 * self.num_attention_heads * self.head_dim

    home_groups = deepseek_v3.DeepSeekV3Config.home_groups


def config_from_dict(cfg: dict, seed: int) -> LingConfig:
    """From a configuration file's dict (the published key names:
    ``num_experts`` is what is held here, ``published.num_experts`` what
    the router addresses)."""
    if any(cfg.get("expert_swiglu_limit_list", [])[:cfg["num_hidden_layers"]]
           ) or any(cfg.get("share_expert_swiglu_limit_list",
                            [])[:cfg["num_hidden_layers"]]):
        Log.fatal("LingLM: a SwiGLU clamp (expert_swiglu_limit_list) on a "
                  "held layer is not supported")
    names = {f.name for f in dataclasses.fields(LingConfig)}
    kw = {k: v for k, v in cfg.items() if k in names and k != "dtype"}
    kw.update(
        n_routed_experts=int(cfg["num_experts"]),
        total_routed_experts=int(cfg.get("published", {}).get(
            "num_experts", cfg["num_experts"])),
        n_shared_experts=int(cfg.get("num_shared_experts", 1)))
    return LingConfig(dtype=jnp.dtype(cfg.get("dtype", "bfloat16")),
                      seed=int(seed), **kw)


# -- the weight law -----------------------------------------------------------
# As deepseek_v3.py's for what the two share (a key per (layer, leaf), routed
# experts by ROUTER OUTPUT index, float32 normal on the device times the
# leaf's std, rounded to the configuration's dtype; router, bias and the
# decay's small leaves stay float32). The reference keeps its own copy.
_LEAVES = ("embed", "head", "w_q", "w_kva", "w_kvb", "w_o", "w_og",
           "w_gate", "w_up", "w_down", "router", "router_bias",
           "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
           "k_qkv", "k_conv", "k_f", "k_dt", "k_alog", "k_beta", "k_g", "k_o")
# MLA: W_q at 1.5 / sqrt(D) (scores of std near 1.5 at the plain 192^-0.5
# scale), W_o at 16 / sqrt(fan_in): a head's output is a softmax average
# over ~100 effective keys at 1,000 positions, 0.1 an element, halved
# again by the gate: 0.8 an element where an FFN adds 0.6 (at 8 the one
# latent layer of seven left its rotation next to nothing in the logits).
# KDA: the taps 0.5 an element (four of them: the convolution keeps the
# scale and three quarters of it comes from the tail); the decay's
# pre-activation N(-5.5, 1.4^2): log a = -5 sigmoid(.) has its median near
# -0.02 and 95% inside (-0.5, -0.002): a memory of tens to hundreds of
# tokens; w_beta at 1.5 / sqrt(D): b spreads over 0.1-0.9; W_o at
# 1 / sqrt(fan_in): N_head leaves unit elements whatever the state's size
_Q_GAIN, _MLA_WO_GAIN = 1.5, 16.0
_TAP_STD = 0.5
_DT_MEAN, _DT_STD, _ALOG_STD = -5.5, 1.0, 0.1
_BETA_GAIN = 1.5


def _leaf_key(seed: int, layer: int, leaf: str):
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
                             layer + 1)
    return jax.random.fold_in(key, _LEAVES.index(leaf))


def init_params(cfg: LingConfig) -> Dict[str, Any]:
    """The share's weights, each leaf one jitted draw on the default
    device (never the whole tree at once, never on the host)."""
    D, H, dh, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim, \
        cfg.dtype
    f32 = jnp.float32
    draws = deepseek_v3.drawers(dt)
    draw = draws[0]
    ones = lambda n: jnp.ones((n,), f32)

    def kda_leaves(k):
        return {
            "norm": ones(D), "o_norm": ones(dh),
            "w_qkv": draw(k("k_qkv"), (D, 3 * H * dh), D ** -0.5, dt),
            "conv": draw(k("k_conv"), (cfg.short_conv_kernel_size,
                                       3 * H * dh), _TAP_STD, f32),
            "w_f": draw(k("k_f"), (D, H * dh), D ** -0.5, dt),
            "dt_bias": _DT_MEAN + draw(k("k_dt"), (H * dh,), _DT_STD, f32),
            "a_log": draw(k("k_alog"), (H,), _ALOG_STD, f32),
            "w_beta": draw(k("k_beta"), (D, H), _BETA_GAIN * D ** -0.5, dt),
            "w_g": draw(k("k_g"), (D, H * dh), D ** -0.5, dt),
            "w_o": draw(k("k_o"), (H * dh, D), (H * dh) ** -0.5, dt)}

    layers = []
    for l in range(cfg.num_hidden_layers):
        k = lambda leaf, l=l: _leaf_key(cfg.seed, l, leaf)
        layers.append({
            "attn": (longcat.draw_mla(cfg, draw, k, _Q_GAIN, _MLA_WO_GAIN)
                     if cfg.is_mla(l) else kda_leaves(k)),
            "ffn_norm": ones(D),
            **deepseek_v3.draw_ffn_layer(cfg, draws, k, l)})
    return {"embed": draw(_leaf_key(cfg.seed, -1, "embed"),
                          (cfg.vocab_size, D), 1.0, dt),
            "head": draw(_leaf_key(cfg.seed, -1, "head"),
                         (D, cfg.vocab_size), D ** -0.5, dt),
            "final_norm": ones(D),
            "layers": layers}


# -- a KDA sublayer -------------------------------------------------------------
def kda_project(cfg: LingConfig, w, x):
    """What a KDA sublayer makes of its normed input ``x`` [T, D] before
    the convolution and beside it: ``(qkv~ [T, 3 H dh] in the model's
    dtype, log_a [T, H, dh] float32, b [T, H] float32)``."""
    f32 = jnp.float32
    H, dh = cfg.num_attention_heads, cfg.head_dim
    T = x.shape[0]
    qkv = jnp.dot(x, w["w_qkv"], preferred_element_type=f32).astype(cfg.dtype)
    f = jnp.dot(x, w["w_f"], preferred_element_type=f32) + w["dt_bias"]
    log_a = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[None, :, None] * f.reshape(T, H, dh))
    b = jax.nn.sigmoid(jnp.dot(x, w["w_beta"], preferred_element_type=f32))
    return qkv, log_a, b


def kda_heads(cfg: LingConfig, y):
    """The convolution's output ``y`` [T, 3 H dh] (float32) through SiLU
    to per-head ``(q, k, v)`` [T, H, dh], q and k L2-normed over the
    head (eps 1e-6), q times ``dh^-0.5``."""
    H, dh = cfg.num_attention_heads, cfg.head_dim
    q, k, v = jnp.split(jax.nn.silu(y).reshape(y.shape[0], 3 * H, dh), 3,
                        axis=1)
    unit = lambda z: z * jax.lax.rsqrt(
        jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * dh ** -0.5, unit(k), v


def kda_output(cfg: LingConfig, w, x, o):
    """The sublayer's tail: ``[N_head(o) * sigmoid(x W_g)] W_o``,
    ``o`` [T, H, dh] float32 -> [T, D] float32."""
    f32 = jnp.float32
    T = x.shape[0]
    g = jax.nn.sigmoid(jnp.dot(x, w["w_g"], preferred_element_type=f32))
    y = rmsnorm(o, w["o_norm"], cfg.rms_norm_eps, f32) * g.reshape(o.shape)
    return jnp.dot(y.reshape(T, -1).astype(cfg.dtype), w["w_o"],
                   preferred_element_type=f32)


def kda_sequence(cfg: LingConfig, w, x, state, tail, length, valid=None):
    """A KDA sublayer over ``x`` [T, D], rows of ONE sequence that
    continue ``state`` [H, dh, dh] and ``tail`` [taps - 1, 3 H dh]; the
    first ``length`` rows are the sequence's (``valid``). Returns ``(out
    [T, D] float32, state, tail, mean log_a a row [T])``."""
    qkv, log_a, b = kda_project(cfg, w, x)
    y, tail = kda.short_conv_chunk(qkv, w["conv"], tail, length)
    q, k, v = kda_heads(cfg, y)
    o, state = kda.kda_chunk(q, k, v, log_a, b, state, valid)
    return kda_output(cfg, w, x, o), state, tail, jnp.mean(log_a, (1, 2))


def _layer_apply(cfg, layer, h, attend, valid=None):
    """One layer on the residual stream ``h`` [T, D] (float32);
    ``attend(w, x)`` runs the layer's attention, of either kind, on the
    normed input. Returns ``(h, counts)``, None for a dense layer."""
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    w = layer["attn"]
    h = h + attend(w, rmsnorm(h, w["norm"], eps, dt))
    u = rmsnorm(h, layer["ffn_norm"], eps, dt)
    if "ffn" in layer:
        return h + swiglu(u, **layer["ffn"]), None
    y, counts = deepseek_v3.expert_layer(cfg, layer, u, valid)
    return h + y, counts


def forward(cfg: LingConfig, params, tokens) -> jax.Array:
    """Full causal forward pass of ONE sequence ``tokens`` [T], no
    cache, the state from zero: logits [T, V] in float32."""
    T = tokens.shape[0]
    H, dh = cfg.num_attention_heads, cfg.head_dim
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def attend(w, x):
        if "w_kva" in w:
            q_nope, q_rope, rows = longcat.mla_project(cfg, w, x, pos)
            return longcat.mla_expanded(cfg, w, q_nope, q_rope, rows, mask,
                                        longcat.head_gate(cfg, w, x))
        return kda_sequence(
            cfg, w, x, jnp.zeros((H, dh, dh), jnp.float32),
            jnp.zeros((cfg.short_conv_kernel_size - 1, cfg.conv_width),
                      cfg.dtype), T)[0]

    for layer in params["layers"]:
        h, _ = _layer_apply(cfg, layer, h, attend)
    return longcat._logits(cfg, params, h)


# -- paged programs ---------------------------------------------------------------
def _walk(cfg, params, h, pools, counters, mla, kda_layer, valid):
    """Every layer over ``h``. ``pools`` is ``(latent, state, tails)``;
    ``mla(w, x, latent, sub) -> (latent, out)`` runs latent layer
    ``sub``, ``kda_layer(w, x, state, tails, j) -> (state, tails, out,
    decay)`` KDA layer ``j`` (``decay``: the summed mean ``log a`` of
    its valid rows). An expert layer's counts go to its row of
    ``counters``, the KDA layers' to the last row."""
    latent, state, tails = pools
    sub = j = 0
    n_valid = jnp.sum(valid.astype(jnp.float32))
    for l, layer in enumerate(params["layers"]):
        def attend(w, x, sub=sub, j=j):
            nonlocal latent, state, tails, counters
            if "w_kva" in w:
                latent, out = mla(w, x, latent, sub)
                return out
            state, tails, out, decay = kda_layer(w, x, state, tails, j)
            counters = counters.at[-1, _DECAY_SUM].add(decay) \
                .at[-1, _KDA_TOKENS].add(n_valid)
            return out

        h, counts = _layer_apply(cfg, layer, h, attend, valid=valid)
        if cfg.is_mla(l):
            sub += 1
        else:
            j += 1
        if counts is not None:
            counters = counters.at[l - cfg.first_k_dense_replace].add(counts)
    return h, (latent, state, tails), counters


def decode_step_paged(cfg: LingConfig, params, latent, state, tails,
                      counters, block_tables, tok, pos, active,
                      t_logical: int, paged_attention=None,
                      kda_pool_step=None):
    """One fused token step over S slots: the latent layers against the
    paged pool (``longcat.decode_step_paged``'s contract), the KDA
    layers against ``state`` [kda layers, S, H, dh, dh] and ``tails``
    [kda layers, S, taps - 1, 3 H dh]. A slot that is not ``active``
    keeps its state and tail bit for bit. ``kda_pool_step``
    (``ops.kda.kda_step_pool``, where the model's ``serving_programs``
    finds it applies) moves a layer's states in place in the pool, one
    pass over each; without it :func:`ops.kda.kda_step` runs on the
    layer's slab. Returns ``(latent, state, tails, counters, next_tok,
    pos)``."""
    rows = longcat.step_rows(latent, block_tables, pos, active)
    h = jnp.take(params["embed"], tok, axis=0).astype(jnp.float32)

    def kda_layer(w, x, state, tails, j):
        qkv, log_a, b = kda_project(cfg, w, x)
        y, tail = kda.short_conv_step(qkv, w["conv"], tails[j])
        q, k, v = kda_heads(cfg, y)
        if kda_pool_step is not None:
            o, state = kda_pool_step(q, k, v, log_a, b, active, state, j)
        else:
            o, new = kda.kda_step(q, k, v, log_a, b, state[j])
            state = state.at[j].set(
                jnp.where(active[:, None, None, None], new, state[j]))
        tails = tails.at[j].set(
            jnp.where(active[:, None, None], tail, tails[j]))
        return state, tails, kda_output(cfg, w, x, o), \
            jnp.sum(jnp.where(active, jnp.mean(log_a, (1, 2)), 0.0))

    h, pools, counters = _walk(
        cfg, params, h, (latent, state, tails), counters,
        lambda w, x, latent, sub: longcat.step_attend(
            cfg, w, x, latent, sub, block_tables, pos, rows, t_logical,
            paged_attention), kda_layer, active)
    return pools + (counters,) + longcat.greedy_next(cfg, params, h, tok,
                                                     pos, active)


def prefill_chunk_paged(cfg: LingConfig, params, latent, state, tails,
                        counters, block_tables, slot, tokens, offset,
                        length, t_logical: int):
    """Incremental prefill of one fixed-size chunk of ONE slot
    (``longcat.prefill_chunk_paged``'s contract). The slot's state and
    tail start from ZERO where the chunk starts a prompt (``offset ==
    0``: whatever an earlier request left there is never read) and from
    the slot's rows otherwise; padded rows are the identity on both. A
    chunk whose table names only the scratch block (warm-up) writes
    neither. Returns ``(latent, state, tails, counters, last_logits
    [V])``."""
    rows = longcat.chunk_rows(latent, block_tables, slot, tokens.shape[0],
                              offset, length, t_logical)
    valid = rows[2]
    first = offset == 0
    live = rows[0][0] != 0
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def kda_layer(w, x, state, tails, j):
        old_s, old_t = state[j, slot], tails[j, slot]
        out, new_s, new_t, decay = kda_sequence(
            cfg, w, x, jnp.where(first, 0, old_s), jnp.where(first, 0, old_t),
            length, valid)
        state = state.at[j, slot].set(jnp.where(live, new_s, old_s))
        tails = tails.at[j, slot].set(jnp.where(live, new_t, old_t))
        return state, tails, out, jnp.sum(jnp.where(valid, decay, 0.0))

    h, pools, counters = _walk(
        cfg, params, h, (latent, state, tails), counters,
        lambda w, x, latent, sub: longcat.chunk_attend(
            cfg, w, x, latent, sub, rows, t_logical), kda_layer, valid)
    counters = counters.at[-1, _RESETS].add(
        (first & live).astype(jnp.float32))
    return pools + (counters, longcat.last_logits(cfg, params, h, length))


def counters_summary(cfg: LingConfig, counts: np.ndarray) -> dict:
    """``eng.stats()``'s keys from the accumulated counters: the expert
    layers' rows as ``deepseek_v3.routing_summary`` reads them, and from
    the last row ``kda_layer_tokens`` ((token, KDA layer) pairs, prefill
    and decode), ``kda_mean_log_decay`` (the mean ``log a`` over them,
    heads and channels: the weight law's design value; 0 or the lower
    bound is a collapsed gate) and ``kda_state_resets`` (slots zeroed by
    a chunk that started a prompt: admissions)."""
    c = np.asarray(counts, np.float64)
    out = deepseek_v3.routing_summary(cfg, c[:-1])
    own = c[-1]
    out["kda_state_resets"] = float(own[_RESETS])
    out["kda_layer_tokens"] = float(own[_KDA_TOKENS])
    if own[_KDA_TOKENS] > 0:
        out["kda_mean_log_decay"] = float(own[_DECAY_SUM] / own[_KDA_TOKENS])
    return out


class LingLM:
    """Serve-only share of a ``bailing_hybrid`` model: weights drawn on
    the device from ``config.seed``; the snapshot contract and the
    engine's seam as :class:`longcat.LongCatLM` has them."""

    def __init__(self, config: LingConfig) -> None:
        c = config
        who = "LingLM"
        deepseek_v3.check_share(who, c)
        if c.n_sublayers < 1 or c.n_kda_layers < 1:
            Log.fatal(f"{who}: {c.num_hidden_layers} layers in groups of "
                      f"{c.layer_group_size} hold no layer of one of the "
                      "two attention kinds")
        if c.n_shared_experts < 1:
            Log.fatal(f"{who}: num_shared_experts {c.n_shared_experts} is "
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
        model's walk with two slot pools, the KDA states (float32) and
        the conv tails; programs ``jit_ling_decode_step`` and
        ``jit_ling_prefill_chunk``. Refused by name beside what every
        latent-pool model refuses: ``prefix_cache`` and (with its own
        reason) ``spec_k``."""
        cfg = self.config
        H, dh, J = cfg.num_attention_heads, cfg.head_dim, cfg.n_kda_layers
        # the one-token step moves the states in place where a head's
        # state is whole tiles on a TPU
        step = functools.partial(decode_step_paged,
                                 kda_pool_step=kda.pool_step(H, dh, dh))
        return longcat.latent_pool_programs(
            cfg, spec, "ling", step, prefill_chunk_paged,
            (cfg.n_expert_layers + 1,
             COUNT_SCALARS + cfg.n_routed_experts + 1),
            lambda delta: counters_summary(cfg, delta),
            slot_pools=(((J, spec.slots, H, dh, dh), jnp.float32),
                        ((J, spec.slots, cfg.short_conv_kernel_size - 1,
                          cfg.conv_width), cfg.dtype)),
            lacking={"prefix_cache": "a block hit restores no recurrent "
                     "state", "spec_k": "no roll-back of a recurrent state "
                     "and no verify step"})
