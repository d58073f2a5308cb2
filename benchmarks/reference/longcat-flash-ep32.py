"""Plain reference for ``longcat-flash-ep32``: LongCat-Flash-Chat's
forward pass, one chip's share of it, in straightforward ``jax.numpy``:
float32 with full-precision products
(``jax.default_matmul_precision("highest")``), no cache, no kernels, the
expanded form of latent attention, a plain loop (a scan) over the held
experts.

It imports nothing of the program and takes nothing the program made.
Sizes are the configuration's: D ``hidden_size``, H heads, ``rq``
``q_lora_rank``, ``rkv`` ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the
nope, rope and value head sizes. ``N`` is RMSNorm (eps ``rms_norm_eps``)
with its own gain at each use.

**MLA sublayer** on x ``[T, D]``: ``c_q = N(x W_qa) * sqrt(D/rq)``;
``q = c_q W_qb``, per head ``[q_nope(dn), q_rope(dr)]``,
``q_rope <- RoPE(q_rope)``. ``[c_raw(rkv), k_rope_raw(dr)] = x W_kva``;
``c = N(c_raw) * sqrt(D/rkv)``; ``k_rope = RoPE(k_rope_raw)``, one for
all heads, not scaled. Per head ``[k_nope(dn), v(dv)] = c W_kvb``.
Scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr)``, causal,
softmax; output ``concat_h(P v_h) W_o``.

**FFN**: ``(silu(x W_g) * (x W_u)) W_d``.

**Router and expert layer** on u: ``p = softmax(u W_r)`` over all
``published.n_routed_experts + zero_expert_num`` outputs; the
``moe_topk`` largest of ``p + b`` are chosen; gate
``g_i = routed_scaling_factor * p_i``, not renormalised.
``MoE(u) = sum over chosen FFN experts i of g_i FFN_i(u) + sum over
chosen identity experts of g_i u``.

**Block**: ``h1 = x + MLA_0(N(x))``; ``u = N(h1)``; ``m = MoE(u)``;
``h2 = h1 + FFN_0(u)``; ``h3 = h2 + MLA_1(N(h2))``;
``y = h3 + FFN_1(N(h3)) + m``. **Model**: embedding, the blocks, ``N``,
the (untied) head.

Departures from the published model, each listed in the configuration's
file: (1) the share: of the FFN experts only ``n_routed_experts``
starting at ``expert_offset`` are held, and what the absent ones would
add is left out (a pick of an absent expert adds nothing); every
identity expert is applied; the vocabulary is a slice and ``num_layers``
blocks are kept. (2) Where the catalog's ``config`` does not say, the
``assumed`` readings: the two ``mla_scale_*`` factors multiply the
normed latents as above; RoPE rotates interleaved pairs
``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/dr)`` (DeepSeek-V3's
pairing); the router is a softmax in float32; ``b`` is zeros. (3)
Weights are random, by the law below (``assumed.weights``).

**The weight law** (this file's own copy): one key per (block, leaf)
from the seed, and per expert by its router output index; float32
normal on the device times the leaf's std (``1/sqrt(fan_in)``; the
embedding 1; ``W_qb`` a quarter and the router 1.2 times of it), rounded
to the configuration's ``dtype`` and upcast; the router stays float32.

``compute`` puts a lower precision in every matrix product's place
(both operands rounded to it): the control.
"""

from __future__ import annotations

import functools
import math

import numpy as np

LEAVES = ("embed", "head", "w_qa", "w_qb", "w_kva", "w_kvb", "w_o",
          "w_gate", "w_up", "w_down", "router", "e_gate", "e_up", "e_down")
QB_GAIN, ROUTER_GAIN = 0.25, 1.2


# -- the weight law ------------------------------------------------------------
def _leaf_key(seed31: int, block: int, leaf: str, sub: int = 0):
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed31) % (2 ** 31 - 1)),
                             block + 1)
    return jax.random.fold_in(key, 16 * LEAVES.index(leaf) + sub)


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


def block_weights(cfg: dict, seed31: int, b: int) -> dict:
    """Block ``b``'s weights, float32 (values of ``cfg["dtype"]``)."""
    import jax
    import jax.numpy as jnp

    D, F, Fe = cfg["hidden_size"], cfg["ffn_hidden_size"], \
        cfg["expert_ffn_hidden_size"]
    H, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    one, many = _drawers(cfg.get("dtype", "bfloat16"))
    n_out = total_experts(cfg) + cfg["zero_expert_num"]
    ones = lambda n: jnp.ones((n,), jnp.float32)

    def mla(j):
        k = lambda leaf: _leaf_key(seed31, b, leaf, j)
        return {"norm": ones(D), "q_norm": ones(rq), "kv_norm": ones(rkv),
                "w_qa": one(k("w_qa"), (D, rq), D ** -0.5),
                "w_qb": one(k("w_qb"), (rq, H * (dn + dr)),
                            QB_GAIN * rq ** -0.5),
                "w_kva": one(k("w_kva"), (D, rkv + dr), D ** -0.5),
                "w_kvb": one(k("w_kvb"), (rkv, H * (dn + dv)), rkv ** -0.5),
                "w_o": one(k("w_o"), (H * dv, D), (H * dv) ** -0.5)}

    def ffn(j):
        k = lambda leaf: _leaf_key(seed31, b, leaf, j)
        return {"norm": ones(D),
                "w_gate": one(k("w_gate"), (D, F), D ** -0.5),
                "w_up": one(k("w_up"), (D, F), D ** -0.5),
                "w_down": one(k("w_down"), (F, D), F ** -0.5)}

    ids = int(cfg.get("expert_offset", 0)) + jnp.arange(
        cfg["n_routed_experts"])
    keys = lambda leaf: jax.vmap(lambda e: jax.random.fold_in(
        _leaf_key(seed31, b, leaf), e))(ids)
    router = jax.random.normal(_leaf_key(seed31, b, "router"), (D, n_out),
                               jnp.float32) * (ROUTER_GAIN * D ** -0.5)
    return {"mla": [mla(0), mla(1)], "ffn": [ffn(0), ffn(1)],
            "router": router, "router_bias": jnp.zeros((n_out,), jnp.float32),
            "e_gate": many(keys("e_gate"), (D, Fe), D ** -0.5),
            "e_up": many(keys("e_up"), (D, Fe), D ** -0.5),
            "e_down": many(keys("e_down"), (Fe, D), Fe ** -0.5)}


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


def rope(x, theta):
    """``x`` [T, ..., d]: pair ``(x[2i], x[2i+1])`` of the token at
    position ``t`` turns by ``t * theta^(-2i/d)``."""
    import jax.numpy as jnp

    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def mla(cfg, mm, w, x, fault=""):
    """One MLA sublayer on the normed ``x`` [T, D], causal, expanded."""
    import jax
    import jax.numpy as jnp

    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    T = x.shape[0]
    sq = math.sqrt(D / rq) if cfg["mla_scale_q_lora"] else 1.0
    skv = math.sqrt(D / rkv) if cfg["mla_scale_kv_lora"] else 1.0
    if fault == "no_mla_scale":
        skv = 1.0
    turn = (lambda a: a) if fault == "no_rope" else \
        (lambda a: rope(a, theta))
    c_q = rmsnorm(mm("td,dr->tr", x, w["w_qa"]), w["q_norm"], eps) * sq
    q = mm("tr,rk->tk", c_q, w["w_qb"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], turn(q[..., dn:])
    kv = mm("td,dk->tk", x, w["w_kva"])
    c = rmsnorm(kv[:, :rkv], w["kv_norm"], eps) * skv
    k_rope = turn(kv[:, rkv:])
    kvb = mm("tc,ck->tk", c, w["w_kvb"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (mm("qhd,khd->hqk", q_nope, k_nope)
         + mm("qhr,kr->hqk", q_rope, k_rope)) / math.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -1e30)
    o = mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(T, H * dv)
    return mm("tk,kd->td", o, w["w_o"])


def ffn(mm, w_gate, w_up, w_down, x):
    import jax

    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def expert_layer(cfg, mm, w, u, identity=True, fault="", router=None):
    """The held experts' part of ``MoE(u)`` (and, with ``identity``, the
    identity experts'): a plain loop over the held experts. ``router``
    replaces the product that makes the router's logits (the control
    rounds every OTHER product, and one control of its own rounds the
    router's)."""
    import jax
    import jax.numpy as jnp

    n_ffn = total_experts(cfg)
    offset = int(cfg.get("expert_offset", 0))
    p = jax.nn.softmax((router or _ops(""))("td,de->te", u, w["router"]), -1)
    _, idx = jax.lax.top_k(p + w["router_bias"], cfg["moe_topk"])
    scale = 1.0 if fault == "gates_unscaled" else \
        float(cfg["routed_scaling_factor"])
    gates = scale * jnp.take_along_axis(p, idx, -1)            # [T, k]
    m = jnp.zeros_like(u)
    if fault != "no_experts":
        # a plain loop over the held experts, one after the other (a
        # scan, so that the compiler sees ONE expert's body)
        def one(m, expert):
            e, w_gate, w_up, w_down = expert
            g = jnp.sum(jnp.where(idx == offset + e, gates, 0.0), -1)
            return m + g[:, None] * ffn(mm, w_gate, w_up, w_down, u), None

        m, _ = jax.lax.scan(one, m, (jnp.arange(cfg["n_routed_experts"]),
                                     w["e_gate"], w["e_up"], w["e_down"]))
    if identity and fault != "no_identity":
        m = m + jnp.sum(jnp.where(idx >= n_ffn, gates, 0.0), -1)[:, None] * u
    return m


def block(cfg, mm, w, h, fault="", router=None):
    eps = cfg["rms_norm_eps"]
    m0, m1 = w["mla"]
    f0, f1 = w["ffn"]
    h = h + mla(cfg, mm, m0, rmsnorm(h, m0["norm"], eps), fault)
    u = rmsnorm(h, f0["norm"], eps)
    m = expert_layer(cfg, mm, w, u, fault=fault, router=router)
    h = h + ffn(mm, f0["w_gate"], f0["w_up"], f0["w_down"], u)
    h = h + mla(cfg, mm, m1, rmsnorm(h, m1["norm"], eps), fault)
    return h + ffn(mm, f1["w_gate"], f1["w_up"], f1["w_down"],
                   rmsnorm(h, f1["norm"], eps)) + m


def logits(cfg: dict, seed31: int, sequences, compute: str = "",
           fault: str = "", router_compute: str = "") -> list:
    """Full causal forward pass of each sequence (1-D int arrays, padded
    here to one length): a list of float32 logits ``[len, V]``. Computed
    BLOCK BY BLOCK: a block's weights are regenerated from the seed, run
    over every sequence, and dropped (the model whole in float32 fits
    nowhere). ``fault`` plants a fault (the tests' controls):
    ``no_experts``, ``no_identity``, ``gates_unscaled``,
    ``no_mla_scale``, ``no_rope``. ``router_compute`` rounds the
    router's own product."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        mm = _ops(compute)
        router = _ops(router_compute)
        outer = outer_weights(cfg, seed31)
        # one of a few lengths, so that a compiled block serves most runs
        T = -(-max(len(s) for s in sequences) // 512) * 512
        toks = np.zeros((len(sequences), T), np.int32)
        for i, s in enumerate(sequences):
            toks[i, :len(s)] = s
        hs = [outer["embed"][jnp.asarray(row)] for row in toks]
        run = jax.jit(lambda w, h: block(cfg, mm, w, h, fault, router))
        for b in range(cfg["num_layers"]):
            w = block_weights(cfg, seed31, b)
            hs = [run(w, h) for h in hs]
            jax.block_until_ready(hs)
            del w
        # weights are ARGUMENTS of every jitted function: a closed-over
        # array is folded into the program as a constant (the head's 400
        # MB took 42 s to compile so, read on the v5e)
        head = jax.jit(lambda g, w, h: mm(
            "td,dv->tv", rmsnorm(h, g, cfg["rms_norm_eps"]), w))
        return [head(outer["final_norm"], outer["head"], h)[:len(s)]
                for h, s in zip(hs, sequences)]


# -- serving: what the served tokens are held against -------------------------
def token_gaps(cfg: dict, seed31: int, sequences, prompt_lens,
               compute: str = "", block_rows: int = 8) -> list:
    """For each sequence (prompt then served tokens) one full causal
    forward pass; returns per sequence the widest gap by which a served
    token's logit lies below the best logit at its position (0 where
    the served token is the reference's own greedy choice). With
    ``compute`` set, the gap of the token that the lower precision puts
    first instead: the control, which need not decode. (``block_rows``
    is ``reference/gpt2-small.py``'s signature; sequences run one at a
    time here.)"""
    import jax.numpy as jnp

    exact = logits(cfg, seed31, sequences)
    low = logits(cfg, seed31, sequences, compute) if compute else None
    out = []
    for i, (s, first) in enumerate(zip(sequences, prompt_lens)):
        lg = exact[i][:-1]
        served = (jnp.argmax(low[i][:-1], -1) if compute
                  else jnp.asarray(s[1:]))
        took = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        gap = (lg.max(-1) - took)[first - 1:]
        out.append(float(gap.max()) if gap.shape[0] else 0.0)
    return out
