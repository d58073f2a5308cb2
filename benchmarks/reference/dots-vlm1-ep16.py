"""Plain reference for ``dots-vlm1-ep16``: the forward pass of
dots.vlm1.inst's language model (DeepSeek-V3's architecture: the
``config.json`` carries its keys letter for letter), one chip's share of
it, in straightforward ``jax.numpy``: float32 with full-precision
products (``jax.default_matmul_precision("highest")``), no cache, no
kernels, the expanded form of latent attention, a plain loop (a scan)
over the held experts.

It imports nothing of the program and takes nothing the program made.
Sizes are the configuration's: D ``hidden_size``, H heads, ``rq``
``q_lora_rank``, ``rkv`` ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the
nope, rope and value head sizes. ``N`` is RMSNorm (eps ``rms_norm_eps``)
with its own gain at each use; no biases; the head is untied.

**Layer** ``l``: ``h' = h + MLA(N(h))``; ``y = h' + F_l(N(h'))``;
``F_l`` is a SwiGLU of width ``intermediate_size`` for the first
``first_k_dense_replace`` layers and ``MoE`` after. **Model**: embedding,
the layers, ``N``, the head.

**MLA** on x ``[T, D]``: ``c_q = N(x W_qa)``; ``q = c_q W_qb``, per head
``[q_nope(dn), q_rope(dr)]``, ``q_rope <- RoPE(q_rope)``.
``[c_raw(rkv), k_rope_raw(dr)] = x W_kva``; ``c = N(c_raw)``;
``k_rope = RoPE(k_rope_raw)``, one for all heads. Per head
``[k_nope(dn), v(dv)] = c W_kvb``. Scores
``(q_nope . k_nope + q_rope . k_rope) * s``, causal, softmax; output
``concat_h(P v_h) W_o``. ``s = (dn + dr)^-0.5 * m^2`` with
``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (YaRN; 1.3689 at factor
40).

**RoPE**: interleaved pairs ``(x[2i], x[2i+1])`` of the ``dr``-wide slice
turn by ``pos * f_i``. YaRN: ``e_i = theta^(-2i/dr)``; ``low = floor(dr
ln(L / (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(dr ln(L /
(beta_slow 2 pi)) / (2 ln theta))`` with ``L`` the original context,
clipped to ``0..dr-1`` (10 and 23 as published); ``r_i = clip((i - low)
/ (high - low), 0, 1)``; ``f_i = e_i (1 - r_i) + e_i / factor r_i``.
Cos and sin are multiplied by the ratio of the ``mscale`` and
``mscale_all_dim`` terms, which is 1 as published (both are 1).

**Router** on u: ``s = sigmoid(u W_r)`` over all
``published.n_routed_experts`` outputs; ``b = s +
e_score_correction_bias``; the outputs lie in ``n_group`` groups of
consecutive experts, a group's score is the sum of its two largest
``b``; the ``topk_group`` best groups are kept and the
``num_experts_per_tok`` largest ``b`` inside them are the picks; gates
``g = s[picks]`` (never ``b``), ``g / (sum g + 1e-20)``, times
``routed_scaling_factor``. ``MoE(u) = sum_k g_k E_k(u) + E_shared(u)``,
each ``E`` a SwiGLU of width ``moe_intermediate_size``.

Departures from the published model, each listed in the configuration's
file: (1) the share: of the routed experts only ``n_routed_experts``
starting at ``expert_offset`` are held, and what the absent ones would
add is left out (a pick of an absent expert adds nothing); the shared
expert is whole; the vocabulary is a slice; ``num_hidden_layers`` layers
are kept, ``first_k_dense_replace`` of them dense. (2) The vision tower
is not here (the catalog's ``config`` holds the language model only):
sequences are token ids. (3) The multi-token-prediction module
(``num_nextn_predict_layers``) is not run: the served forward pass does
not use it. (4) Outputs outside the kept groups are masked with -inf
before the top-k where the published code fills them with 0.0: the same
picks wherever ``num_experts_per_tok`` kept outputs have ``b > 0``,
which holds for sigmoid scores and a bias of a few hundredths (the 8th
of 128 kept scores is far above 0). (5) Weights are random, by the law
below (``assumed.weights``).

**The weight law** (this file's own copy): one key per (layer, leaf)
from the seed, and per routed expert by its router output index; float32
normal on the device times the leaf's std (``1/sqrt(fan_in)``; the
embedding 1; ``W_qb`` 0.8 of it; ``W_o`` 4 times it; the bias 0.02),
rounded to the configuration's ``dtype`` and upcast; router and bias
stay float32.

``compute`` puts a lower precision in every matrix product's place
(both operands rounded to it) but the router's: the control.
"""

from __future__ import annotations

import functools
import math

import numpy as np

LEAVES = ("embed", "head", "w_qa", "w_qb", "w_kva", "w_kvb", "w_o",
          "w_gate", "w_up", "w_down", "router", "router_bias",
          "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
QB_GAIN, BIAS_STD, WO_GAIN = 0.8, 0.02, 4.0
# planted faults, each a control that `correct` has to catch: the first
# four in the routed path (the held experts' sum dropped, the gates left
# unnormalised, all groups kept, the picks taken by s and not s + bias),
# then the shared expert dropped, YaRN's m^2 left off the softmax scale,
# the rotary slice unrotated
ROUTED_FAULTS = ("no_routed", "gates_unnormalised", "no_group_limit",
                 "no_bias")
FAULTS = ROUTED_FAULTS + ("no_shared", "no_mscale", "no_rope")


# -- the weight law ------------------------------------------------------------
def _leaf_key(seed31: int, layer: int, leaf: str):
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed31) % (2 ** 31 - 1)),
                             layer + 1)
    return jax.random.fold_in(key, LEAVES.index(leaf))


@functools.cache
def _drawers(dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def draw(key, shape, std):
        x = jax.random.normal(key, shape, jnp.float32) * std
        return x.astype(dtype).astype(jnp.float32)

    one = jax.jit(draw, static_argnums=(1, 2))
    many = jax.jit(lambda keys, shape, std: jax.vmap(
        lambda k: draw(k, shape, std))(keys), static_argnums=(1, 2))
    return one, many


def total_experts(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def layer_weights(cfg: dict, seed31: int, l: int) -> dict:
    """Layer ``l``'s weights, float32 (values of ``cfg["dtype"]``): its
    MLA, then a dense FFN (``l < first_k_dense_replace``) or router,
    bias, the held experts and the shared expert."""
    import jax
    import jax.numpy as jnp

    D, F, Fe = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    H, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    one, many = _drawers(cfg.get("dtype", "bfloat16"))
    ones = lambda n: jnp.ones((n,), jnp.float32)
    k = lambda leaf: _leaf_key(seed31, l, leaf)
    w = {"norm": ones(D), "q_norm": ones(rq), "kv_norm": ones(rkv),
         "ffn_norm": ones(D),
         "w_qa": one(k("w_qa"), (D, rq), D ** -0.5),
         "w_qb": one(k("w_qb"), (rq, H * (dn + dr)), QB_GAIN * rq ** -0.5),
         "w_kva": one(k("w_kva"), (D, rkv + dr), D ** -0.5),
         "w_kvb": one(k("w_kvb"), (rkv, H * (dn + dv)), rkv ** -0.5),
         "w_o": one(k("w_o"), (H * dv, D), WO_GAIN * (H * dv) ** -0.5)}
    if l < cfg["first_k_dense_replace"]:
        w.update(w_gate=one(k("w_gate"), (D, F), D ** -0.5),
                 w_up=one(k("w_up"), (D, F), D ** -0.5),
                 w_down=one(k("w_down"), (F, D), F ** -0.5))
        return w
    E = total_experts(cfg)
    ids = int(cfg.get("expert_offset", 0)) + jnp.arange(
        cfg["n_routed_experts"])
    keys = lambda leaf: jax.vmap(lambda e: jax.random.fold_in(k(leaf), e))(ids)
    w.update(
        router=jax.random.normal(k("router"), (D, E), jnp.float32)
        * D ** -0.5,
        router_bias=jax.random.normal(k("router_bias"), (E,), jnp.float32)
        * BIAS_STD,
        e_gate=many(keys("e_gate"), (D, Fe), D ** -0.5),
        e_up=many(keys("e_up"), (D, Fe), D ** -0.5),
        e_down=many(keys("e_down"), (Fe, D), Fe ** -0.5))
    if cfg["n_shared_experts"]:
        Fs = cfg["n_shared_experts"] * Fe
        w.update(s_gate=one(k("s_gate"), (D, Fs), D ** -0.5),
                 s_up=one(k("s_up"), (D, Fs), D ** -0.5),
                 s_down=one(k("s_down"), (Fs, D), Fs ** -0.5))
    return w


def outer_weights(cfg: dict, seed31: int) -> dict:
    import jax.numpy as jnp

    D, V = cfg["hidden_size"], cfg["vocab_size"]
    one, _ = _drawers(cfg.get("dtype", "bfloat16"))
    return {"embed": one(_leaf_key(seed31, -1, "embed"), (V, D), 1.0),
            "head": one(_leaf_key(seed31, -1, "head"), (D, V), D ** -0.5),
            "final_norm": jnp.ones((D,), jnp.float32)}


# -- the equations ---------------------------------------------------------------
def _ops(compute: str):
    import jax.numpy as jnp

    if compute:
        low = jnp.dtype(compute)
        q = lambda x: x.astype(low).astype(jnp.float32)
    else:
        q = lambda x: x
    return lambda eq, a, b: jnp.einsum(eq, q(a), q(b))


def rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn(cfg: dict) -> tuple:
    """``(frequencies [dr/2], m)``: the rotary slice's angular
    frequencies under the configuration's ``rope_scaling`` and the
    factor whose square multiplies the softmax scale."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / d)
    y = cfg.get("rope_scaling")
    if not y:
        return e.astype(np.float32), 1.0
    turns_at = lambda n: d * math.log(
        y["original_max_position_embeddings"] / (n * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(y["beta_fast"])), 0)
    high = min(math.ceil(turns_at(y["beta_slow"])), d - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    term = lambda scale: 0.1 * scale * math.log(y["factor"]) + 1.0 \
        if y["factor"] > 1 else 1.0
    if term(y["mscale"]) != term(y["mscale_all_dim"]):
        raise NotImplementedError(
            "cos and sin scaled by mscale / mscale_all_dim terms other "
            "than 1")
    return ((e * (1 - r) + e / y["factor"] * r).astype(np.float32),
            term(y["mscale_all_dim"]))


def rope(x, freqs):
    """``x`` [T, ..., d]: pair ``(x[2i], x[2i+1])`` of the token at
    position ``t`` turns by ``t * freqs[i]``."""
    import jax.numpy as jnp

    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def mla(cfg, mm, w, x, fault=""):
    """The MLA sublayer on the normed ``x`` [T, D], causal, expanded."""
    import jax
    import jax.numpy as jnp

    H = cfg["num_attention_heads"]
    rkv = cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    T = x.shape[0]
    freqs, m = yarn(cfg)
    if fault == "no_mscale":
        m = 1.0
    turn = (lambda a: a) if fault == "no_rope" else \
        (lambda a: rope(a, freqs))
    c_q = rmsnorm(mm("td,dr->tr", x, w["w_qa"]), w["q_norm"], eps)
    q = mm("tr,rk->tk", c_q, w["w_qb"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
    kv = mm("td,dk->tk", x, w["w_kva"])
    c = rmsnorm(kv[:, :rkv], w["kv_norm"], eps)
    k_rope = turn(kv[:, rkv:])
    kvb = mm("tc,ck->tk", c, w["w_kvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (mm("qhd,khd->hqk", q_nope, k_nope)
         + mm("qhr,kr->hqk", q_rope, k_rope)) * ((dn + dr) ** -0.5 * m * m)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(T, H * dv)
    return mm("tk,kd->td", o, w["w_o"])


def ffn(mm, w_gate, w_up, w_down, x):
    import jax

    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def route(cfg, w, u, fault="", router=None):
    """``(picks [T, k], gates [T, k], kept groups [T, n_group])``.
    ``router`` replaces the product that makes the router's logits."""
    import jax
    import jax.numpy as jnp

    G, k = cfg["n_group"], cfg["num_experts_per_tok"]
    keep = G if fault == "no_group_limit" else cfg["topk_group"]
    s = jax.nn.sigmoid((router or _ops(""))("td,de->te", u, w["router"]))
    b = s if fault == "no_bias" else s + w["router_bias"]
    T, E = s.shape
    grouped = b.reshape(T, G, E // G)
    group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    order = jnp.argsort(-group_score, -1, stable=True)[:, :keep]
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], order].set(True)
    inside = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(T, E)
    picks = jnp.argsort(-inside, -1, stable=True)[:, :k]
    g = jnp.take_along_axis(s, picks, -1)
    if cfg.get("norm_topk_prob", True) and fault != "gates_unnormalised":
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return picks, float(cfg["routed_scaling_factor"]) * g, kept


def expert_layer(cfg, mm, w, u, shared=True, fault="", router=None):
    """The held experts' part of ``MoE(u)`` and (``shared``) the shared
    expert's: a plain loop over the held experts."""
    import jax
    import jax.numpy as jnp

    offset = int(cfg.get("expert_offset", 0))
    picks, gates, _ = route(cfg, w, u, fault, router)
    # a plain loop over the held experts, one after the other (a scan,
    # so that the compiler sees ONE expert's body)
    def one(m, expert):
        e, w_gate, w_up, w_down = expert
        g = jnp.sum(jnp.where(picks == offset + e, gates, 0.0), -1)
        return m + g[:, None] * ffn(mm, w_gate, w_up, w_down, u), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(cfg["n_routed_experts"]),
                         w["e_gate"], w["e_up"], w["e_down"]))
    if fault == "no_routed":
        m = jnp.zeros_like(u)
    if shared and "s_gate" in w and fault != "no_shared":
        m = m + ffn(mm, w["s_gate"], w["s_up"], w["s_down"], u)
    return m


def layer(cfg, mm, w, h, fault="", router=None):
    eps = cfg["rms_norm_eps"]
    h = h + mla(cfg, mm, w, rmsnorm(h, w["norm"], eps), fault)
    u = rmsnorm(h, w["ffn_norm"], eps)
    if "router" in w:
        return h + expert_layer(cfg, mm, w, u, fault=fault, router=router)
    return h + ffn(mm, w["w_gate"], w["w_up"], w["w_down"], u)


def padded_logits(cfg: dict, seed31: int, sequences, compute: str = "",
                  fault: str = "", router_compute: str = "") -> tuple:
    """Full causal forward pass of each sequence (1-D int arrays):
    ``(tokens [n, T] padded with 0 to one length, a list of float32
    logits [T, V])``; rows past a sequence's length are padding's, and
    no row before them depends on it. Computed LAYER BY LAYER: a
    layer's weights are regenerated from the seed, run over every
    sequence, and dropped (a dense layer is 2.3 GB in float32, an
    expert layer with 16 held experts 3.8 GB: the model whole fits
    nowhere). ``fault`` plants a fault (the tests' and the cell's
    controls): one of :data:`FAULTS`. ``router_compute`` rounds the
    router's own product."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        mm = _ops(compute)
        router = _ops(router_compute)
        outer = outer_weights(cfg, seed31)
        # one of a few lengths, so that a compiled layer serves most runs
        T = -(-max(len(s) for s in sequences) // 512) * 512
        toks = np.zeros((len(sequences), T), np.int32)
        for i, s in enumerate(sequences):
            toks[i, :len(s)] = s
        hs = [outer["embed"][jnp.asarray(row)] for row in toks]
        # weights are ARGUMENTS of every jitted function (a closed-over
        # array is folded into the program as a constant)
        run = jax.jit(lambda w, h: layer(cfg, mm, w, h, fault, router))
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed31, l)
            hs = [run(w, h) for h in hs]
            jax.block_until_ready(hs)
            del w
        head = jax.jit(lambda g, w, h: mm(
            "td,dv->tv", rmsnorm(h, g, cfg["rms_norm_eps"]), w))
        return toks, [head(outer["final_norm"], outer["head"], h)
                      for h in hs]


def logits(cfg: dict, seed31: int, sequences, compute: str = "",
           fault: str = "", router_compute: str = "") -> list:
    """:func:`padded_logits` cut to each sequence's length: a list of
    float32 logits ``[len, V]``."""
    _, padded = padded_logits(cfg, seed31, sequences, compute, fault,
                              router_compute)
    return [lg[:len(s)] for lg, s in zip(padded, sequences)]


# -- serving: what the served tokens are held against -------------------------
def token_gap_tables(cfg: dict, seed31: int, sequences, prompt_lens,
                     compute: str = "", fault: str = "", exact=None) -> list:
    """For each sequence (prompt then served tokens) one full causal
    forward pass; returns per sequence, for every served token, the gap
    by which its logit lies below the best logit at its position (0
    where the served token is the reference's own greedy choice), as a
    float32 numpy array. With ``compute`` or ``fault`` set, the gap of
    the token that the lower precision or the faulty reference puts
    first instead: a control, which need not decode. ``exact`` hands in
    ``padded_logits(cfg, seed31, sequences)`` where several controls
    share it. Every device operation runs at the padded length (a
    length of its own would be a compile of its own); the host cuts."""
    import jax
    import jax.numpy as jnp

    toks, lgs = exact or padded_logits(cfg, seed31, sequences)
    other = padded_logits(cfg, seed31, sequences, compute, fault)[1] \
        if compute or fault else None
    # row t holds the logits of the token at t + 1
    gap = jax.jit(lambda lg, took: lg.max(-1) - jnp.take_along_axis(
        lg, took[:, None], -1)[:, 0])
    first_of = jax.jit(lambda lg: jnp.argmax(lg, -1))
    out = []
    for i, (s, first) in enumerate(zip(sequences, prompt_lens)):
        took = first_of(other[i])[:-1] if other is not None \
            else jnp.asarray(toks[i, 1:])
        g = np.asarray(gap(lgs[i][:-1], took), np.float32)
        out.append(g[first - 1:len(s) - 1])
    return out


def token_gaps(cfg: dict, seed31: int, sequences, prompt_lens,
               compute: str = "") -> list:
    """Per sequence the widest of :func:`token_gap_tables`' gaps."""
    return [float(t.max()) if t.size else 0.0 for t in token_gap_tables(
        cfg, seed31, sequences, prompt_lens, compute)]
