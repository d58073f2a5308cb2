"""Plain reference for ``mellum2-12b-l8``: the forward pass of
Mellum2-12B-A2.5B's language model (``model_type`` ``mellum``), the
layers held on one chip, in straightforward ``jax.numpy``: float32 with
full-precision products (``jax.default_matmul_precision("highest")``),
no cache, no chunks, no kernels: attention over the whole sequence under
a mask, in blocks of queries so that 32 heads over 4,096 positions fit;
a plain loop (a scan) over the experts.

It imports nothing of the program and takes nothing the program made.
Sizes are the configuration's: D ``hidden_size``, H query heads, KV
key/value heads, ``dh`` ``head_dim``, E ``num_experts``, F
``moe_intermediate_size``, W ``sliding_window``. ``N`` is RMSNorm (eps
``rms_norm_eps``) with its own gain at each use; no biases; the head is
untied.

**Layer** ``l`` (kind ``layer_types[l]``): ``h' = h + A_l(N(h))``;
``y = h' + MoE(N(h'))``. **Model**: embedding, the layers, ``N``, the
head.

**Attention** on x ``[T, D]``: ``q = x W_q`` (H heads), ``k = x W_k``,
``v = x W_v`` (KV heads); rotary on q and k over the whole head,
rotate-half (the pair ``(x[i], x[i + dh/2])`` of the token at ``t``
turns by ``t f_i``): a sliding layer at ``f_i = theta^(-2i/dh)``, a full
layer at YaRN's frequencies as transformers' ``_compute_yarn_parameters``
reckons them (``truncate`` true) with cos and sin times
``attention_factor``; head ``h`` reads KV head ``h // (H / KV)``;
scores ``q . k / sqrt(dh)``; key ``j`` visible to query ``i`` where ``0
<= i - j`` and, on a sliding layer, ``i - j < W``; softmax; ``W_o``.

**MoE** on u: ``p = softmax(u W_r)`` over the E outputs (float32 whatever
``compute``); the ``num_experts_per_tok`` largest are the picks; gates
``p / sum(p of the picks)`` (``norm_topk_prob``); ``MoE(u) = sum_k g_k
E_k(u)``, each E a SwiGLU of width F; no shared expert, no bias.

Departures from the published model, each listed in the configuration's
file: ``num_hidden_layers`` layers kept; the multi-token-prediction head
the model card names is not run; weights are random by the law below
(this file's own copy of ``assumed.weights``).

``compute`` puts a lower precision in every matrix product's place
(both operands rounded to it) but the router's: the control. Operands
rounded to bfloat16 or to an 8-bit float are multiplied as bfloat16 with
float32 accumulation, which is exact for them.
"""

from __future__ import annotations

import functools
import math

import numpy as np

LEAVES = ("embed", "head", "w_q", "w_k", "w_v", "w_o", "router",
          "e_gate", "e_up", "e_down")
Q_GAIN, WO_GAIN, DOWN_GAIN = 1.5, 8.0, 2.5
# queries a block of the attention: [H, 512, T] float32 scores at a time
QUERY_BLOCK = 512
# planted faults, each a control that ``correct`` has to catch
FAULTS = ("window_ignored", "window_off_by_one", "yarn_factor_dropped",
          "yarn_on_sliding", "full_default_frequencies",
          "gates_not_normalised", "gqa_mod_groups", "experts_dropped")


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


def layer_weights(cfg: dict, seed31: int, l: int) -> dict:
    """Layer ``l``'s weights, float32 (values of ``cfg["dtype"]``; the
    router float32)."""
    import jax
    import jax.numpy as jnp

    D, H, KV, dh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    dt = jnp.dtype(cfg.get("dtype", "bfloat16"))
    draw, many = _drawers(dt.name)
    one = lambda key, shape, std: draw(key, shape, std, dt)
    k = lambda leaf: _leaf_key(seed31, l, leaf)
    keys = lambda leaf: jax.vmap(
        lambda e: jax.random.fold_in(k(leaf), e))(jnp.arange(E))
    return {"norm": jnp.ones((D,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "w_q": one(k("w_q"), (D, H * dh), Q_GAIN * D ** -0.5),
            "w_k": one(k("w_k"), (D, KV * dh), D ** -0.5),
            "w_v": one(k("w_v"), (D, KV * dh), D ** -0.5),
            "w_o": one(k("w_o"), (H * dh, D), WO_GAIN * (H * dh) ** -0.5),
            "router": draw(k("router"), (D, E), D ** -0.5, jnp.float32),
            "e_gate": many(keys("e_gate"), (D, F), D ** -0.5),
            "e_up": many(keys("e_up"), (D, F), D ** -0.5),
            "e_down": many(keys("e_down"), (F, D), DOWN_GAIN * F ** -0.5)}


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


def frequencies(rope: dict, dh: int) -> tuple:
    """``(f [dh/2] float32, cos/sin scale)`` of one ``rope_parameters``
    entry: ``theta^(-2i/dh)``, or YaRN's blend of it and its ``1/factor``
    over the pairs that turn between ``beta_slow`` and ``beta_fast``
    times in ``original_max_position_embeddings`` positions (floor and
    ceil of the bounds, clipped to ``[0, dh - 1]``)."""
    theta = float(rope["rope_theta"])
    base = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    extra = 1.0 / base
    if rope.get("rope_type", "default") != "yarn":
        return extra.astype(np.float32), 1.0
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])
    dim = lambda turns: dh * math.log(orig / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def rope_half(x, freqs, scale):
    """``x`` [T, heads, d]: the pair ``(x[i], x[i + d/2])`` of the token
    at position ``t`` turns by ``t * freqs[i]``, cos and sin times
    ``scale``."""
    import jax.numpy as jnp

    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(freqs)[None, None]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg, mm, w, x, full: bool, fault=""):
    """The attention sublayer on the normed ``x`` [T, D], in blocks of
    :data:`QUERY_BLOCK` queries (``T`` a multiple of it, or less)."""
    import jax
    import jax.numpy as jnp

    H, KV, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    T = x.shape[0]
    kinds = cfg["rope_parameters"]
    rope = kinds["full_attention" if full or fault == "yarn_on_sliding"
                 else "sliding_attention"]
    freqs, scale = frequencies(rope, dh)
    if full and fault == "yarn_factor_dropped":
        scale = 1.0
    if full and fault == "full_default_frequencies":
        freqs = frequencies(kinds["sliding_attention"], dh)[0]
    q = rope_half(mm("td,dk->tk", x, w["w_q"]).reshape(T, H, dh), freqs,
                  scale)
    k = rope_half(mm("td,dk->tk", x, w["w_k"]).reshape(T, KV, dh), freqs,
                  scale)
    v = mm("td,dk->tk", x, w["w_v"]).reshape(T, KV, dh)
    group = np.arange(H) % KV if fault == "gqa_mod_groups" \
        else np.arange(H) // (H // KV)
    k, v = k[:, group], v[:, group]                         # [T, H, dh]
    window = {"window_ignored": T + 1, "window_off_by_one":
              cfg["sliding_window"] + 1}.get(fault, cfg["sliding_window"])
    B = min(QUERY_BLOCK, T)
    keys = jnp.arange(T)

    def block(args):
        qb, start = args                                    # [B, H, dh]
        s = mm("qhd,khd->hqk", qb, k) / math.sqrt(dh)
        d = (start + jnp.arange(B))[:, None] - keys[None, :]
        seen = (d >= 0) & (d < window) if not full else d >= 0
        s = jnp.where(seen[None], s, -1e30)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (q.reshape(T // B, B, H, dh),
                            jnp.arange(0, T, B)))
    return mm("tk,kd->td", o.reshape(T, H * dh), w["w_o"])


def ffn(mm, w_gate, w_up, w_down, x):
    import jax

    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def expert_layer(cfg, mm, w, u, fault="", router=None):
    """``MoE(u)``: the router, then a plain loop over the experts."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax((router or _ops(""))("td,de->te", u, w["router"]), -1)
    picks = jnp.argsort(-p, -1, stable=True)[:, :cfg["num_experts_per_tok"]]
    g = jnp.take_along_axis(p, picks, -1)
    if cfg.get("norm_topk_prob", True) and fault != "gates_not_normalised":
        g = g / g.sum(-1, keepdims=True)

    # one after the other (a scan, so that the compiler sees ONE
    # expert's body)
    def one(m, expert):
        e, w_gate, w_up, w_down = expert
        gate = jnp.sum(jnp.where(picks == e, g, 0.0), -1)
        return m + gate[:, None] * ffn(mm, w_gate, w_up, w_down, u), None

    m, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (jnp.arange(cfg["num_experts"]),
                         w["e_gate"], w["e_up"], w["e_down"]))
    return jnp.zeros_like(u) if fault == "experts_dropped" else m


def layer(cfg, mm, w, h, full: bool, fault="", router=None):
    eps = cfg["rms_norm_eps"]
    h = h + attention(cfg, mm, w, rmsnorm(h, w["norm"], eps), full, fault)
    return h + expert_layer(cfg, mm, w, rmsnorm(h, w["ffn_norm"], eps),
                            fault, router)


class _Logits:
    """A list of float32 logits ``[T, V]`` a sequence, each made from the
    sequence's final normed rows when it is read: eight sequences of
    4,096 positions over a 98,304-row vocabulary are 12.9 GB of logits
    at once, a chip's worth."""

    def __init__(self, head, rows):
        self.head, self.rows = head, rows

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.head(self.rows[i])

    def __iter__(self):
        return (self[i] for i in range(len(self)))


def padded_logits(cfg: dict, seed31: int, sequences, compute: str = "",
                  fault: str = "", router_compute: str = "",
                  prompt_lens=None) -> tuple:
    """Full causal forward pass of each sequence (1-D int arrays):
    ``(tokens [n, T] padded with 0 to one length, the float32 logits
    [T, V] of each)``, the logits a sequence made when read
    (:class:`_Logits`); rows past a sequence's length are padding's, and
    no row before them depends on it (attention is causal). Computed
    LAYER BY LAYER: a layer's weights are regenerated from the seed, run
    over every sequence, and dropped. ``fault`` plants a fault (the
    tests' and the cell's controls): one of :data:`FAULTS`.
    ``router_compute`` rounds the router's own product; ``prompt_lens``
    is taken for the driver's sake and not needed by any fault here."""
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
        run = jax.jit(lambda w, h, full: layer(cfg, mm, w, h, full, fault,
                                               router),
                      static_argnums=2)
        kinds = cfg["layer_types"]
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed31, l)
            full = kinds[l] == "full_attention"
            hs = [run(w, h, full) for h in hs]
            jax.block_until_ready(hs)
            del w
        final = jax.jit(lambda g, h: rmsnorm(h, g, cfg["rms_norm_eps"]))
        head = jax.jit(lambda w, x: mm("td,dv->tv", x, w))

        def logits_of(x):
            with jax.default_matmul_precision("highest"):
                return head(outer["head"], x)

        return toks, _Logits(logits_of, [final(outer["final_norm"], h)
                                         for h in hs])


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
    share it. Every device operation runs at the padded length; the
    host cuts."""
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
