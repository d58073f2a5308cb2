"""Plain reference for ``ling3-flash-ep4``: the forward pass of
Ling-3.0-flash's language model (``model_type`` ``bailing_hybrid``), one
chip's share of it, in straightforward ``jax.numpy``: float32 with
full-precision products (``jax.default_matmul_precision("highest")``),
no cache, no chunks, no kernels: the KDA recurrence token by token (a
scan), its convolution a plain causal convolution over the whole
sequence, latent attention expanded, a plain loop (a scan) over the held
experts.

It imports nothing of the program and takes nothing the program made.
Sizes are the configuration's: D ``hidden_size``, H heads, ``dh``
``head_dim`` (KDA's ``dk = dv``), ``rkv`` ``kv_lora_rank``, ``dn`` /
``dr`` / ``dv`` the latent layer's nope, rope and value head sizes.
``N`` is RMSNorm (eps ``rms_norm_eps``) with its own gain at each use;
no biases; the head is untied.

**Layer** ``i``: ``h' = h + A_i(N(h))``; ``y = h' + F_i(N(h'))``.
``A_i`` is MLA where ``(i + 1) % layer_group_size == 0`` and KDA
otherwise; ``F_i`` is a SwiGLU of width ``intermediate_size`` for the
first ``first_k_dense_replace`` layers and ``MoE`` after. **Model**:
embedding, the layers, ``N``, the head.

**KDA** on x ``[T, D]`` (arXiv:2510.26692; where a key of the
configuration is silent, Kimi Linear's published layer):
``[q~, k~, v~] = x W_qkv`` (each D -> H dh); each channel passes a causal
convolution over time of ``short_conv_kernel_size`` taps, zeros before
the sequence's start, ``y_t = sum_j c_j x_(t - K + 1 + j)``, then SiLU;
per head ``q = q' / sqrt(|q'|^2 + 1e-6) dh^-0.5``, ``k = k' /
sqrt(|k'|^2 + 1e-6)``, ``v = v'``; ``log a = kda_lower_bound *
sigmoid(exp(A_log[h]) (x W_f + dt_bias))`` per head and key channel;
``b = sigmoid(x w_beta)`` a head; state ``S`` [dh, dh] a head, zero at
the start: ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t
v_t^T``, ``o_t = S_t^T q_t``; output ``[N_head(o) * sigmoid(x W_g)]
W_o`` with ``N_head`` an RMSNorm over each head's ``dh`` with one gain
of ``dh``.

**MLA** on x: ``q = x W_q``, per head ``[q_nope(dn), q_rope(dr)]``;
``[c_raw(rkv), k_rope_raw(dr)] = x W_kva``; ``c = N(c_raw)``; RoPE on
``q_rope`` and ``k_rope`` (interleaved pairs ``(x[2i], x[2i+1])`` turn by
``pos * theta^(-2i/dr)``); per head ``[k_nope(dn), v(dv)] = c W_kvb``;
scores ``(q_nope . k_nope + q_rope . k_rope) (dn + dr)^-0.5``, causal,
softmax; head ``h``'s output times ``sigmoid(x w_og[h])``; then ``W_o``.

**Router**: as ``dots-vlm1-ep16``'s (sigmoid scores over all
``published.num_experts`` outputs, ``n_group`` groups of consecutive
outputs scored by their two largest biased scores, ``topk_group`` kept,
``num_experts_per_tok`` picks by biased score inside them, gates from
the unbiased scores, normalised, times ``routed_scaling_factor``), in
float32 whatever ``compute``. ``MoE(u) = sum_k g_k E_k(u) +
E_shared(u)``, each ``E`` a SwiGLU of width ``moe_intermediate_size``.

Departures from the published model, each listed in the configuration's
file: the share (of the routed experts only ``num_experts`` from
``expert_offset`` are held and what the absent ones would add is left
out; a vocabulary slice; ``num_hidden_layers`` layers kept); the
multi-token-prediction module is not run; the SwiGLU clamp is 0 for
every kept layer and is not run; weights are random by the law below
(this file's own copy of ``assumed.weights``).

``compute`` puts a lower precision in every matrix product's place
(both operands rounded to it) but the router's and the recurrence's own
(the state is float32 by the configuration): the control. Operands
rounded to bfloat16 or to an 8-bit float are multiplied as bfloat16 with
float32 accumulation, which is exact for them.
"""

from __future__ import annotations

import functools

import numpy as np

LEAVES = ("embed", "head", "w_q", "w_kva", "w_kvb", "w_o", "w_og",
          "w_gate", "w_up", "w_down", "router", "router_bias",
          "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
          "k_qkv", "k_conv", "k_f", "k_dt", "k_alog", "k_beta", "k_g", "k_o")
Q_GAIN, MLA_WO_GAIN, BIAS_STD = 1.5, 16.0, 0.02
TAP_STD, DT_MEAN, DT_STD, ALOG_STD, BETA_GAIN = 0.5, -5.5, 1.0, 0.1, 1.5
# planted faults, each a control that `correct` has to catch. The first
# two need to know where the served program cuts a sequence: at every
# FAULT_CHUNK positions of the prompt and where the prompt ends (the
# first decode step)
FAULT_CHUNK = 512
FAULTS = ("kda_state_dropped", "kda_decay_ignored", "kda_beta_one",
          "conv_tail_dropped", "kda_qk_unnormalised", "mla_gate_dropped",
          "rotary_unrotated", "held_experts_dropped",
          "gates_not_normalised", "group_limit_ignored",
          "router_bias_ignored", "shared_expert_dropped")


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

    def draw(key, shape, std, dtype):
        x = jax.random.normal(key, shape, jnp.float32) * std
        return x.astype(dtype).astype(jnp.float32)

    one = jax.jit(draw, static_argnums=(1, 2, 3))
    many = jax.jit(lambda keys, shape, std: jax.vmap(
        lambda k: draw(k, shape, std, jnp.dtype(dtype_name)))(keys),
        static_argnums=(1, 2))
    return one, many


def total_experts(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def is_mla(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def layer_weights(cfg: dict, seed31: int, l: int) -> dict:
    """Layer ``l``'s weights, float32 (values of ``cfg["dtype"]``; the
    router, its bias, the taps, ``dt_bias`` and ``A_log`` float32): its
    attention of either kind, then a dense FFN or router, bias, the held
    experts and the shared expert."""
    import jax
    import jax.numpy as jnp

    D, F, Fe = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    H, dh, rkv = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    f32 = jnp.float32
    draw, many = _drawers(dt.name)
    one = lambda key, shape, std: draw(key, shape, std, dt)
    ones = lambda n: jnp.ones((n,), f32)
    k = lambda leaf: _leaf_key(seed31, l, leaf)
    w = {"norm": ones(D), "ffn_norm": ones(D)}
    if is_mla(cfg, l):
        w.update(
            kv_norm=ones(rkv),
            w_q=one(k("w_q"), (D, H * (dn + dr)), Q_GAIN * D ** -0.5),
            w_og=one(k("w_og"), (D, H), D ** -0.5),
            w_kva=one(k("w_kva"), (D, rkv + dr), D ** -0.5),
            w_kvb=one(k("w_kvb"), (rkv, H * (dn + dv)), rkv ** -0.5),
            w_o=one(k("w_o"), (H * dv, D), MLA_WO_GAIN * (H * dv) ** -0.5))
    else:
        w.update(
            o_norm=ones(dh),
            k_qkv=one(k("k_qkv"), (D, 3 * H * dh), D ** -0.5),
            k_conv=draw(k("k_conv"), (cfg["short_conv_kernel_size"],
                                      3 * H * dh), TAP_STD, f32),
            k_f=one(k("k_f"), (D, H * dh), D ** -0.5),
            k_dt=DT_MEAN + draw(k("k_dt"), (H * dh,), DT_STD, f32),
            k_alog=draw(k("k_alog"), (H,), ALOG_STD, f32),
            k_beta=one(k("k_beta"), (D, H), BETA_GAIN * D ** -0.5),
            k_g=one(k("k_g"), (D, H * dh), D ** -0.5),
            k_o=one(k("k_o"), (H * dh, D), (H * dh) ** -0.5))
    if l < cfg["first_k_dense_replace"]:
        w.update(w_gate=one(k("w_gate"), (D, F), D ** -0.5),
                 w_up=one(k("w_up"), (D, F), D ** -0.5),
                 w_down=one(k("w_down"), (F, D), F ** -0.5))
        return w
    E = total_experts(cfg)
    ids = int(cfg.get("expert_offset", 0)) + jnp.arange(cfg["num_experts"])
    keys = lambda leaf: jax.vmap(lambda e: jax.random.fold_in(k(leaf), e))(ids)
    Fs = cfg.get("num_shared_experts", 1) * Fe
    w.update(
        router=draw(k("router"), (D, E), D ** -0.5, f32),
        router_bias=draw(k("router_bias"), (E,), BIAS_STD, f32),
        e_gate=many(keys("e_gate"), (D, Fe), D ** -0.5),
        e_up=many(keys("e_up"), (D, Fe), D ** -0.5),
        e_down=many(keys("e_down"), (Fe, D), Fe ** -0.5),
        s_gate=one(k("s_gate"), (D, Fs), D ** -0.5),
        s_up=one(k("s_up"), (D, Fs), D ** -0.5),
        s_down=one(k("s_down"), (Fs, D), Fs ** -0.5))
    return w


def outer_weights(cfg: dict, seed31: int) -> dict:
    import jax.numpy as jnp

    D, V = cfg["hidden_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    draw, _ = _drawers(dt.name)
    return {"embed": draw(_leaf_key(seed31, -1, "embed"), (V, D), 1.0, dt),
            "head": draw(_leaf_key(seed31, -1, "head"), (D, V), D ** -0.5,
                         dt),
            "final_norm": jnp.ones((D,), jnp.float32)}


# -- the equations ---------------------------------------------------------------
def _ops(compute: str):
    import jax.numpy as jnp

    if not compute:
        return lambda eq, a, b: jnp.einsum(eq, a, b)
    low = jnp.dtype(compute)
    # every bfloat16 and 8-bit-float value is a bfloat16 value, and a
    # product of two is exact in the float32 the sum is kept in
    carry = jnp.bfloat16 if low.itemsize == 1 or low == jnp.bfloat16 \
        else jnp.float32
    q = lambda x: x.astype(low).astype(carry)
    return lambda eq, a, b: jnp.einsum(
        eq, q(a), q(b), preferred_element_type=jnp.float32)


def rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


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

    H, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    T = x.shape[0]
    freqs = (float(cfg["rope_theta"])
             ** (-2.0 * np.arange(dr // 2, dtype=np.float64) / dr)
             ).astype(np.float32)
    turn = (lambda a: a) if fault == "rotary_unrotated" else \
        (lambda a: rope(a, freqs))
    q = mm("td,dk->tk", x, w["w_q"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
    kv = mm("td,dk->tk", x, w["w_kva"])
    c = rmsnorm(kv[:, :rkv], w["kv_norm"], cfg["rms_norm_eps"])
    k_rope = turn(kv[:, rkv:])
    kvb = mm("tc,ck->tk", c, w["w_kvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (mm("qhd,khd->hqk", q_nope, k_nope)
         + mm("qhr,kr->hqk", q_rope, k_rope)) * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    if fault != "mla_gate_dropped":
        o = o * jax.nn.sigmoid(mm("td,dh->th", x, w["w_og"]))[..., None]
    return mm("tk,kd->td", o.reshape(T, H * dv), w["w_o"])


def kda(cfg, mm, w, x, seg, fault=""):
    """The KDA sublayer on the normed ``x`` [T, D]: the convolution over
    the whole sequence, then the recurrence token by token from a zero
    state. ``seg`` [T] numbers the stretches between the places where
    the served program cuts the sequence; only two planted faults read
    it."""
    import jax
    import jax.numpy as jnp

    H, dh, K = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["short_conv_kernel_size"]
    T = x.shape[0]
    raw = mm("td,dk->tk", x, w["k_qkv"])                    # [T, 3 H dh]
    y = w["k_conv"][K - 1] * raw
    for lag in range(1, K):
        back = jnp.pad(raw, ((lag, 0), (0, 0)))[:T]
        if fault == "conv_tail_dropped":    # nothing from before a cut
            back = jnp.where(
                (jnp.pad(seg, (lag, 0), constant_values=-1)[:T]
                 == seg)[:, None], back, 0.0)
        y = y + w["k_conv"][K - 1 - lag] * back
    q, k, v = jnp.split(jax.nn.silu(y).reshape(T, 3 * H, dh), 3, axis=1)
    if fault != "kda_qk_unnormalised":
        unit = lambda z: z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True)
                                      + 1e-6)
        q, k = unit(q), unit(k)
    q = q * dh ** -0.5
    f = (mm("td,dk->tk", x, w["k_f"]) + w["k_dt"]).reshape(T, H, dh)
    a = jnp.exp(float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(w["k_alog"])[None, :, None] * f))
    if fault == "kda_decay_ignored":
        a = jnp.ones_like(a)
    b = jax.nn.sigmoid(mm("td,dh->th", x, w["k_beta"]))
    if fault == "kda_beta_one":
        b = jnp.ones_like(b)
    cut = jnp.pad(seg, (1, 0), constant_values=0)[:T] != seg \
        if fault == "kda_state_dropped" else jnp.zeros((T,), bool)

    def token(S, t):
        q, k, v, a, b, cut = t
        S = jnp.where(cut, 0.0, S) * a[..., None]           # Diag(a) S
        u = v - jnp.sum(k[..., None] * S, axis=1)           # v - S^T k
        S = S + (b[:, None] * k)[..., None] * u[:, None, :]
        return S, jnp.sum(q[..., None] * S, axis=1)         # S^T q

    _, o = jax.lax.scan(token, jnp.zeros((H, dh, dh), jnp.float32),
                        (q, k, v, a, b, cut))
    o = rmsnorm(o, w["o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(mm("td,dk->tk", x, w["k_g"])).reshape(T, H, dh)
    return mm("tk,kd->td", o.reshape(T, H * dh), w["k_o"])


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
    keep = G if fault == "group_limit_ignored" else cfg["topk_group"]
    s = jax.nn.sigmoid((router or _ops(""))("td,de->te", u, w["router"]))
    b = s if fault == "router_bias_ignored" else s + w["router_bias"]
    T, E = s.shape
    grouped = b.reshape(T, G, E // G)
    group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    order = jnp.argsort(-group_score, -1, stable=True)[:, :keep]
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], order].set(True)
    inside = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(T, E)
    picks = jnp.argsort(-inside, -1, stable=True)[:, :k]
    g = jnp.take_along_axis(s, picks, -1)
    if cfg.get("norm_topk_prob", True) and fault != "gates_not_normalised":
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return picks, float(cfg["routed_scaling_factor"]) * g, kept


def expert_layer(cfg, mm, w, u, shared=True, fault="", router=None):
    """The held experts' part of ``MoE(u)`` and (``shared``) the shared
    expert's: a plain loop over the held experts."""
    import jax
    import jax.numpy as jnp

    offset = int(cfg.get("expert_offset", 0))
    picks, gates, _ = route(cfg, w, u, fault, router)

    # one after the other (a scan, so that the compiler sees ONE
    # expert's body)
    def one(m, expert):
        e, w_gate, w_up, w_down = expert
        g = jnp.sum(jnp.where(picks == offset + e, gates, 0.0), -1)
        return m + g[:, None] * ffn(mm, w_gate, w_up, w_down, u), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(cfg["num_experts"]),
                         w["e_gate"], w["e_up"], w["e_down"]))
    if fault == "held_experts_dropped":
        m = jnp.zeros_like(u)
    if shared and fault != "shared_expert_dropped":
        m = m + ffn(mm, w["s_gate"], w["s_up"], w["s_down"], u)
    return m


def layer(cfg, mm, w, h, seg, fault="", router=None):
    eps = cfg["rms_norm_eps"]
    x = rmsnorm(h, w["norm"], eps)
    h = h + (mla(cfg, mm, w, x, fault) if "w_kva" in w
             else kda(cfg, mm, w, x, seg, fault))
    u = rmsnorm(h, w["ffn_norm"], eps)
    if "router" in w:
        return h + expert_layer(cfg, mm, w, u, fault=fault, router=router)
    return h + ffn(mm, w["w_gate"], w["w_up"], w["w_down"], u)


def _segments(T: int, prompt_len) -> np.ndarray:
    """Which stretch each position lies in when the served program cuts
    the sequence at every ``FAULT_CHUNK`` positions of the prompt and at
    the prompt's end (None: a prompt of the whole length)."""
    pos = np.arange(T)
    p = T if prompt_len is None else int(prompt_len)
    return (np.minimum(pos, p - 1) // FAULT_CHUNK
            + (pos >= p)).astype(np.int32)


def padded_logits(cfg: dict, seed31: int, sequences, compute: str = "",
                  fault: str = "", router_compute: str = "",
                  prompt_lens=None) -> tuple:
    """Full causal forward pass of each sequence (1-D int arrays):
    ``(tokens [n, T] padded with 0 to one length, a list of float32
    logits [T, V])``; rows past a sequence's length are padding's, and
    no row before them depends on it (both attention kinds are causal).
    Computed LAYER BY LAYER: a layer's weights are regenerated from the
    seed, run over every sequence, and dropped (an expert layer with 128
    held experts is 3.3 GB in float32). ``fault`` plants a fault (the
    tests' and the cell's controls): one of :data:`FAULTS`;
    ``prompt_lens`` tells the two that drop what crosses a cut where the
    prompts end. ``router_compute`` rounds the router's own product."""
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
        segs = [jnp.asarray(_segments(
            T, None if prompt_lens is None else prompt_lens[i]))
            for i in range(len(sequences))]
        hs = [outer["embed"][jnp.asarray(row)] for row in toks]
        # weights are ARGUMENTS of every jitted function (a closed-over
        # array is folded into the program as a constant)
        run = jax.jit(lambda w, h, seg: layer(cfg, mm, w, h, seg, fault,
                                              router))
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed31, l)
            hs = [run(w, h, seg) for h, seg in zip(hs, segs)]
            jax.block_until_ready(hs)
            del w
        head = jax.jit(lambda g, w, h: mm(
            "td,dv->tv", rmsnorm(h, g, cfg["rms_norm_eps"]), w))
        return toks, [head(outer["final_norm"], outer["head"], h)
                      for h in hs]


def logits(cfg: dict, seed31: int, sequences, compute: str = "",
           fault: str = "", router_compute: str = "",
           prompt_lens=None) -> list:
    """:func:`padded_logits` cut to each sequence's length: a list of
    float32 logits ``[len, V]``."""
    _, padded = padded_logits(cfg, seed31, sequences, compute, fault,
                              router_compute, prompt_lens)
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
    other = padded_logits(cfg, seed31, sequences, compute, fault,
                          prompt_lens=prompt_lens)[1] \
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
