"""Plain reference for ``glm5-ep16``: the forward pass of GLM-5's language
model (``model_type`` ``glm_moe_dsa``: DeepSeek-V3's layer pattern with
DeepSeek-V3.2's learned sparse attention), one chip's share of it, in
straightforward ``jax.numpy``: float32 with full-precision products
(``jax.default_matmul_precision("highest")``), no cache, no chunks, no
kernels: the indexer's scores, the selection and the expanded latent
attention over the whole sequence under a mask, in blocks of queries so
that ``[T, T]`` scores at T = 8,704 fit; a plain loop (a scan) over the
held experts.

It imports nothing of the program and takes nothing the program made.
Sizes are the configuration's: D ``hidden_size``, H heads, ``rq``
``q_lora_rank``, ``rkv`` ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the
nope, rope and value head sizes, ``Hi`` ``index_n_heads``, ``dI``
``index_head_dim``, ``K`` ``index_topk``. ``N`` is RMSNorm (eps
``rms_norm_eps``) with its own gain at each use; no biases; the head is
untied.

**Layer** ``l``: ``h' = h + MLA(N(h))``; ``y = h' + F_l(N(h'))``; ``F_l``
a SwiGLU of width ``intermediate_size`` for the first
``first_k_dense_replace`` layers, ``MoE`` after. **Model**: embedding,
the layers, ``N``, the head.

**MLA** on x ``[T, D]``: ``c_q = N(x W_qa)``; ``q = c_q W_qb``, a head
``[q_nope(dn), q_rope(dr)]``, ``q_rope <- RoPE(q_rope)``;
``[c_raw(rkv), k_rope_raw(dr)] = x W_kva``; ``c = N(c_raw)``; ``k_rope =
RoPE(k_rope_raw)``, one for all heads; a head's ``[k_nope(dn), v(dv)] =
c W_kvb``. Scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn +
dr)``; query ``t`` sees position ``s`` where ``s <= t`` and ``s`` is in
``S(t)``; softmax; output ``concat_h(P v_h) W_o``.

**RoPE**: interleaved pairs ``(x[2i], x[2i+1])`` of a ``dr``-wide slice
turn by ``pos * theta^(-2i/dr)``, ``theta`` ``rope_parameters.rope_theta``
(no scaling).

**The indexer** (every layer): ``q^I = c_q W_Iq``, ``Hi`` heads of
``dI``; ``k^I = LayerNorm(x W_Ik)`` (eps 1e-6, with gain and bias), one
a token; the first ``dr`` values of both turned by RoPE (interleaved, as
``indexer_rope_interleave`` says); ``w = (x W_Iw) Hi^-1/2 dI^-1/2``.
``I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s))``, in float32. ``S(t)``:
the ``min(K, t + 1)`` positions ``s <= t`` of largest ``I``, as
``jax.lax.top_k`` picks them (a tie to the lower position).

**Router** on u: ``s = sigmoid(u W_r)`` over all
``published.n_routed_experts`` outputs; ``b = s +
e_score_correction_bias``; with ``n_group`` 1 and ``topk_group`` 1 the
``num_experts_per_tok`` largest ``b`` are the picks; gates ``g =
s[picks]``, ``g / (sum g + 1e-20)``, times ``routed_scaling_factor``.
``MoE(u) = sum_k g_k E_k(u) + E_shared(u)``, each ``E`` a SwiGLU of width
``moe_intermediate_size``.

Departures from the published model, each listed in the configuration's
file: (1) the share: of the routed experts only ``n_routed_experts``
from ``expert_offset`` are held, and what the absent ones would add is
left out; the shared expert is whole; the vocabulary is a slice;
``num_hidden_layers`` layers are kept, ``first_k_dense_replace`` of them
dense. (2) The multi-token-prediction module is not run. (3) The
indexer's products are exact here, where the published inference code
rounds ``q^I`` and ``k^I`` to FP8 after a Hadamard rotation (orthonormal:
it changes no score in exact arithmetic). (4) Weights are random, by the
law below.

**The weight law** (this file's own copy): one key per (layer, leaf)
from the seed, and per routed expert by its router output index;
float32 normal on the device times the leaf's std (``1/sqrt(fan_in)``;
the embedding 1; ``W_qb`` 1.5 times it; ``W_o`` 4 times it; the bias
0.02; the key LayerNorm's gain ``1 + 0.5 z`` and bias ``0.5 z``), rounded
to the configuration's ``dtype`` and upcast; the router, its bias and
the key norm's gain and bias stay float32.

``compute`` puts a lower precision in every matrix product's place
(both operands rounded to it) but the router's: the control. Operands
rounded to bfloat16 or to an 8-bit float are multiplied as bfloat16
with float32 accumulation on the TPU, which is exact for them.
"""

from __future__ import annotations

import functools

import numpy as np

LEAVES = ("embed", "head", "w_qa", "w_qb", "w_kva", "w_kvb", "w_o",
          "w_gate", "w_up", "w_down", "router", "router_bias",
          "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
          "w_iq", "w_ik", "ik_gain", "ik_bias", "w_iw")
QB_GAIN, WO_GAIN, BIAS_STD, KEY_NORM_SPREAD = 1.5, 4.0, 0.02, 0.5
KEY_NORM_EPS = 1e-6
# queries a block of the attention: [H, 256, T] float32 scores at a time
QUERY_BLOCK = 256
# planted faults, each a control that ``correct`` has to catch: in the
# selection (the indexer ignored: every earlier position read; half the
# top-k; one position more; the ReLU dropped; the index keys and queries
# unrotated; the key LayerNorm dropped; the head weights ignored; the
# keys made by the NEXT layer's key projection and norm), then in the
# expert layer (the held experts dropped, the gates not normalised, the
# shared expert dropped)
FAULTS = ("dense", "topk_half", "topk_one_wider", "no_relu",
          "index_unrotated", "no_key_norm", "weights_ignored",
          "neighbour_keys", "no_routed", "gates_unnormalised", "no_shared")


# -- the weight law -----------------------------------------------------------
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
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def layer_weights(cfg: dict, seed31: int, l: int) -> dict:
    """Layer ``l``'s weights, float32 (values of ``cfg["dtype"]``): MLA
    and the indexer, then a dense FFN (``l < first_k_dense_replace``) or
    router, bias, the held experts and the shared expert."""
    import jax
    import jax.numpy as jnp

    D, F, Fe = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    H, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    Hi, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    draw, many = _drawers(dt.name)
    one = lambda key, shape, std: draw(key, shape, std, dt)
    f32 = jnp.float32
    ones = lambda n: jnp.ones((n,), f32)
    k = lambda leaf: _leaf_key(seed31, l, leaf)
    w = {"norm": ones(D), "q_norm": ones(rq), "kv_norm": ones(rkv),
         "ffn_norm": ones(D),
         "w_qa": one(k("w_qa"), (D, rq), D ** -0.5),
         "w_qb": one(k("w_qb"), (rq, H * (dn + dr)), QB_GAIN * rq ** -0.5),
         "w_kva": one(k("w_kva"), (D, rkv + dr), D ** -0.5),
         "w_kvb": one(k("w_kvb"), (rkv, H * (dn + dv)), rkv ** -0.5),
         "w_o": one(k("w_o"), (H * dv, D), WO_GAIN * (H * dv) ** -0.5),
         "w_iq": one(k("w_iq"), (rq, Hi * dI), rq ** -0.5),
         "w_iw": one(k("w_iw"), (D, Hi), D ** -0.5),
         **index_key_weights(cfg, seed31, l)}
    if l < cfg["first_k_dense_replace"]:
        w.update(w_gate=one(k("w_gate"), (D, F), D ** -0.5),
                 w_up=one(k("w_up"), (D, F), D ** -0.5),
                 w_down=one(k("w_down"), (F, D), F ** -0.5))
        return w
    E = total_experts(cfg)
    ids = int(cfg.get("expert_offset", 0)) + jnp.arange(
        cfg["n_routed_experts"])
    keys = lambda leaf: jax.vmap(lambda e: jax.random.fold_in(k(leaf), e))(ids)
    Fs = cfg["n_shared_experts"] * Fe
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


def index_key_weights(cfg: dict, seed31: int, l: int) -> dict:
    """Layer ``l``'s key projection ``w_ik`` and key norm (gain, bias),
    float32."""
    import jax.numpy as jnp

    D, dI = cfg["hidden_size"], cfg["index_head_dim"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    draw, _ = _drawers(dt.name)
    k = lambda leaf: _leaf_key(seed31, l, leaf)
    return {"w_ik": draw(k("w_ik"), (D, dI), D ** -0.5, dt),
            "ik_gain": 1.0 + draw(k("ik_gain"), (dI,), KEY_NORM_SPREAD,
                                  jnp.float32),
            "ik_bias": draw(k("ik_bias"), (dI,), KEY_NORM_SPREAD,
                            jnp.float32)}


def outer_weights(cfg: dict, seed31: int) -> dict:
    import jax.numpy as jnp

    D, V = cfg["hidden_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    draw, _ = _drawers(dt.name)
    return {"embed": draw(_leaf_key(seed31, -1, "embed"), (V, D), 1.0, dt),
            "head": draw(_leaf_key(seed31, -1, "head"), (D, V), D ** -0.5,
                         dt),
            "final_norm": jnp.ones((D,), jnp.float32)}


# -- the equations ------------------------------------------------------------
def _ops(compute: str):
    import jax.numpy as jnp

    if not compute:
        return lambda eq, a, b: jnp.einsum(eq, a, b)
    import jax

    low = jnp.dtype(compute)
    # every bfloat16 and 8-bit-float value is a bfloat16 value, and a
    # product of two is exact in the float32 the sum is kept in: carried
    # as bfloat16 on the TPU (one pass of the matrix unit in place of
    # six), as float32 elsewhere (the CPU has no bfloat16 batched dot)
    carry = jnp.bfloat16 if (low.itemsize == 1 or low == jnp.bfloat16) \
        and jax.default_backend() == "tpu" else jnp.float32
    q = lambda x: x.astype(low).astype(carry)
    return lambda eq, a, b: jnp.einsum(
        eq, q(a), q(b), preferred_element_type=jnp.float32)


def rmsnorm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def layer_norm(x, gain, bias):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + KEY_NORM_EPS) * gain + bias


def rope(x, cfg: dict):
    """``x`` [T, ..., dr]: pair ``(x[2i], x[2i+1])`` of the token at
    position ``t`` turns by ``t theta^(-2i/dr)``."""
    import jax.numpy as jnp

    T, d = x.shape[0], x.shape[-1]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    freqs = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(
        np.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)[None]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def indexer(cfg, mm, w, x, c_q, fault="", next_w=None):
    """``(q^I [T, Hi, dI], k^I [T, dI], w [T, Hi])`` of the layer.
    ``next_w``: the next layer's weights, whose key projection and norm
    make the keys under the fault ``neighbour_keys``."""
    import jax.numpy as jnp

    Hi, dI, dr = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    T = x.shape[0]
    kw = next_w if fault == "neighbour_keys" else w
    q = mm("tr,rk->tk", c_q, w["w_iq"]).reshape(T, Hi, dI)
    k = mm("td,dk->tk", x, kw["w_ik"])
    if fault != "no_key_norm":
        k = layer_norm(k, kw["ik_gain"], kw["ik_bias"])
    if fault != "index_unrotated":
        q = jnp.concatenate([rope(q[..., :dr], cfg), q[..., dr:]], -1)
        k = jnp.concatenate([rope(k[..., :dr], cfg), k[..., dr:]], -1)
    weights = mm("td,dh->th", x, w["w_iw"]) * (Hi ** -0.5 * dI ** -0.5)
    if fault == "weights_ignored":
        weights = jnp.ones_like(weights)
    return q, k, weights


def mla(cfg, mm, w, x, fault="", next_w=None, queries=None):
    """The MLA sublayer with its indexer on the normed ``x`` [T, D], in
    blocks of :data:`QUERY_BLOCK` queries (``T`` a multiple of it, or
    less): each block's index scores, each query's selection by
    ``jax.lax.top_k``, then the expanded attention under causal mask AND
    selection. With ``queries`` [Q] (positions) also the positions each
    of those queries read, ``[Q, T]`` bool: ``(out, read)``."""
    import jax
    import jax.numpy as jnp

    H, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    T = x.shape[0]
    K = {"topk_half": cfg["index_topk"] // 2,
         "topk_one_wider": cfg["index_topk"] + 1}.get(fault,
                                                      cfg["index_topk"])
    K = min(K, T)
    c_q = rmsnorm(mm("td,dr->tr", x, w["w_qa"]), w["q_norm"], eps)
    q = mm("tr,rk->tk", c_q, w["w_qb"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], cfg)
    kv = mm("td,dk->tk", x, w["w_kva"])
    c = rmsnorm(kv[:, :rkv], w["kv_norm"], eps)
    k_rope = rope(kv[:, rkv:], cfg)
    kvb = mm("tc,ck->tk", c, w["w_kvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    iq, ik, iw = indexer(cfg, mm, w, x, c_q, fault, next_w)
    B = min(QUERY_BLOCK, T)
    keys = jnp.arange(T)
    relu = (lambda a: a) if fault == "no_relu" else jax.nn.relu

    def block(args):
        qn, qr, bq, bw, start = args
        causal = (start + jnp.arange(B))[:, None] >= keys[None, :]
        score = jnp.sum(bw[:, :, None] * relu(mm("bjd,td->bjt", bq, ik)), 1)
        chosen = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), K)[1]
        picked = jnp.zeros((B, T), bool).at[
            jnp.arange(B)[:, None], chosen].set(True)
        seen = causal if fault == "dense" else causal & picked
        s = (mm("qhd,khd->hqk", qn, k_nope)
             + mm("qhr,kr->hqk", qr, k_rope)) / np.sqrt(dn + dr)
        s = jnp.where(seen[None], s, -1e30)
        out = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        if queries is None:
            return out
        at = queries - start
        return out, seen[jnp.clip(at, 0, B - 1)] & ((at >= 0)
                                                    & (at < B))[:, None]

    n = T // B
    o = jax.lax.map(block, (q_nope.reshape(n, B, H, dn),
                            q_rope.reshape(n, B, H, dr),
                            iq.reshape((n, B) + iq.shape[1:]),
                            iw.reshape(n, B, -1), jnp.arange(0, T, B)))
    if queries is None:
        return mm("tk,kd->td", o.reshape(T, H * dv), w["w_o"])
    o, read = o
    return mm("tk,kd->td", o.reshape(T, H * dv), w["w_o"]), read.any(0)


def ffn(mm, w_gate, w_up, w_down, x):
    import jax

    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def route(cfg, w, u, fault="", router=None):
    """``(picks [T, k], gates [T, k])``: the ``n_group`` 1 router, a plain
    top-k of ``s + bias``. ``router`` replaces the product that makes the
    router's logits."""
    import jax
    import jax.numpy as jnp

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise NotImplementedError("a group-limited router")
    s = jax.nn.sigmoid((router or _ops(""))("td,de->te", u, w["router"]))
    picks = jax.lax.top_k(s + w["router_bias"],
                          cfg["num_experts_per_tok"])[1]
    g = jnp.take_along_axis(s, picks, -1)
    if fault != "gates_unnormalised":
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return picks, float(cfg["routed_scaling_factor"]) * g


def expert_layer(cfg, mm, w, u, shared=True, fault="", router=None):
    """The held experts' part of ``MoE(u)`` and (``shared``) the shared
    expert's: a plain loop over the held experts."""
    import jax
    import jax.numpy as jnp

    offset = int(cfg.get("expert_offset", 0))
    picks, gates = route(cfg, w, u, fault, router)

    def one(m, expert):
        e, w_gate, w_up, w_down = expert
        g = jnp.sum(jnp.where(picks == offset + e, gates, 0.0), -1)
        return m + g[:, None] * ffn(mm, w_gate, w_up, w_down, u), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(cfg["n_routed_experts"]),
                         w["e_gate"], w["e_up"], w["e_down"]))
    if fault == "no_routed":
        m = jnp.zeros_like(u)
    if shared and fault != "no_shared":
        m = m + ffn(mm, w["s_gate"], w["s_up"], w["s_down"], u)
    return m


def layer(cfg, mm, w, h, fault="", router=None, next_w=None, queries=None):
    """The layer on ``h``; with ``queries``, ``(h, read)`` as
    :func:`mla` has ``read``."""
    eps = cfg["rms_norm_eps"]
    a = mla(cfg, mm, w, rmsnorm(h, w["norm"], eps), fault, next_w, queries)
    a, read = a if queries is not None else (a, None)
    h = h + a
    u = rmsnorm(h, w["ffn_norm"], eps)
    if "router" in w:
        h = h + expert_layer(cfg, mm, w, u, fault=fault, router=router)
    else:
        h = h + ffn(mm, w["w_gate"], w["w_up"], w["w_down"], u)
    return h if queries is None else (h, read)


class _Logits:
    """A list of float32 logits ``[T, V]`` a sequence, each made from the
    sequence's final normed rows when it is read: eight sequences of
    8,704 positions over a 19,360-row vocabulary are 5.4 GB of logits at
    once. ``selected``: per sequence the positions its sampled queries
    read in each layer, ``[L, Q, T]`` bool (:func:`padded_logits`'s
    ``queries``), else None."""

    def __init__(self, head, rows, selected=None):
        self.head, self.rows, self.selected = head, rows, selected

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.head(self.rows[i])

    def __iter__(self):
        return (self[i] for i in range(len(self)))


def padded_logits(cfg: dict, seed31: int, sequences, compute: str = "",
                  fault: str = "", router_compute: str = "",
                  queries=None) -> tuple:
    """Full causal forward pass of each sequence (1-D int arrays):
    ``(tokens [n, T] padded with 0 to one length, the float32 logits
    [T, V] of each)``, the logits made when read (:class:`_Logits`);
    rows past a sequence's length are padding's, and no row before them
    depends on it (attention and selection are causal). Computed LAYER
    BY LAYER: a layer's weights are regenerated from the seed, run over
    every sequence, and dropped. ``fault`` plants a fault (the tests'
    and the cell's controls): one of :data:`FAULTS`. ``router_compute``
    rounds the router's own product. ``queries``: per sequence the
    positions [Q] of the queries whose selections go to the logits'
    ``selected``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        mm = _ops(compute)
        router = _ops(router_compute)
        outer = outer_weights(cfg, seed31)
        # one of a few lengths, so that a compiled layer serves most runs
        n = max(len(s) for s in sequences)
        T = -(-n // QUERY_BLOCK) * QUERY_BLOCK if n > QUERY_BLOCK else n
        toks = np.zeros((len(sequences), T), np.int32)
        for i, s in enumerate(sequences):
            toks[i, :len(s)] = s
        hs = [outer["embed"][jnp.asarray(row)] for row in toks]
        # weights are ARGUMENTS of every jitted function (a closed-over
        # array is folded into the program as a constant)
        run = jax.jit(lambda w, h, nxt, qs: layer(cfg, mm, w, h, fault,
                                                  router, nxt, qs))
        qs = [None] * len(hs) if queries is None \
            else [jnp.asarray(q, jnp.int32) for q in queries]
        reads = [[] for _ in hs]
        L = cfg["num_hidden_layers"]
        for l in range(L):
            w = layer_weights(cfg, seed31, l)
            nxt = index_key_weights(cfg, seed31, (l + 1) % L) \
                if fault == "neighbour_keys" else None
            hs = [run(w, h, nxt, q) for h, q in zip(hs, qs)]
            if queries is not None:
                for r, (_, read) in zip(reads, hs):
                    r.append(np.asarray(read))
                hs = [h for h, _ in hs]
            jax.block_until_ready(hs)
            del w, nxt
        final = jax.jit(lambda g, h: rmsnorm(h, g, cfg["rms_norm_eps"]))
        head = jax.jit(lambda w, x: mm("td,dv->tv", x, w))

        def logits_of(x):
            with jax.default_matmul_precision("highest"):
                return head(outer["head"], x)

        return toks, _Logits(
            logits_of, [final(outer["final_norm"], h) for h in hs],
            None if queries is None else [np.stack(r) for r in reads])


def logits(cfg: dict, seed31: int, sequences, compute: str = "",
           fault: str = "", router_compute: str = "") -> list:
    """:func:`padded_logits` cut to each sequence's length: a list of
    float32 logits ``[len, V]``."""
    _, padded = padded_logits(cfg, seed31, sequences, compute, fault,
                              router_compute)
    return [lg[:len(s)] for lg, s in zip(padded, sequences)]


# -- serving: what the served tokens are held against -------------------------
def token_gap_tables(cfg: dict, seed31: int, sequences, prompt_lens,
                     compute: str = "", fault: str = "", exact=None,
                     other=None) -> list:
    """For each sequence (prompt then served tokens) one full causal
    forward pass; returns per sequence, for every served token, the gap
    by which its logit lies below the best logit at its position (0
    where the served token is the reference's own greedy choice), as a
    float32 numpy array. With ``compute`` or ``fault`` set, the gap of
    the token that the lower precision or the faulty reference puts
    first instead: a control, which need not decode. ``exact`` hands in
    ``padded_logits(cfg, seed31, sequences)`` where several controls
    share it, ``other`` the control's own padded logits where they are
    made already. Every device operation runs at the padded length; the
    host cuts."""
    import jax
    import jax.numpy as jnp

    toks, lgs = exact or padded_logits(cfg, seed31, sequences)
    if other is None and (compute or fault):
        other = padded_logits(cfg, seed31, sequences, compute, fault)
    other = other[1] if other is not None else None
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
