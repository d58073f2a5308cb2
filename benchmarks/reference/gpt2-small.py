"""Plain reference for ``gpt2-small``: the language model's forward
pass, loss, gradients and momentum step in straightforward
``jax.numpy``, float32 with full-precision products, no kernels, no
cache, no batching tricks.

It imports nothing of the program and takes nothing the program made.
Weights come from the seed by the init law the configuration states
(normal, 1/sqrt(fan_in), positions 0.02, norms 1; one numpy stream in
the order embed, pos, w_q, w_k, w_v, w_o, w_ff1, w_ff2).

Departures from the published GPT-2, all the program's own and listed
in the configuration: RMSNorm for LayerNorm, no biases. What the
configuration states is kept: parameters and momentum hold ``dtype``
(bfloat16) and the step is taken in it; everything between them is
float32. ``compute`` puts a lower precision in the matmuls' place (both
operands rounded to it): the control.
"""

from __future__ import annotations

import functools

import numpy as np

LEAVES = ("embed", "pos", "ln1_g", "ln2_g", "w_q", "w_k", "w_v", "w_o",
          "w_ff1", "w_ff2", "ln_f_g")


def init_params(cfg: dict, seed31: int, dtype):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed31)
    V, D, L, F, T = cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"], \
        cfg["n_inner"], cfg["n_positions"]
    s, sf = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)

    def mk(shape, scale):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    p = {"embed": mk((V, D), s), "pos": mk((T, D), 0.02)}
    p["ln1_g"] = jnp.ones((L, D), dtype)
    p["ln2_g"] = jnp.ones((L, D), dtype)
    for name, shape, scale in (("w_q", (L, D, D), s), ("w_k", (L, D, D), s),
                               ("w_v", (L, D, D), s), ("w_o", (L, D, D), s),
                               ("w_ff1", (L, D, F), s),
                               ("w_ff2", (L, F, D), sf)):
        p[name] = mk(shape, scale)
    p["ln_f_g"] = jnp.ones((D,), dtype)
    return p


def _ops(compute: str):
    import jax
    import jax.numpy as jnp

    if compute:
        low = jnp.dtype(compute)
        # rounded going forward, the identity going back
        q = lambda x: x + jax.lax.stop_gradient(
            x.astype(low).astype(jnp.float32) - x)
    else:
        q = lambda x: x
    hi = jax.lax.Precision.HIGHEST
    return lambda eq, a, b: jnp.einsum(eq, q(a), q(b), precision=hi)


def rmsnorm(x, g):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * g


def block(cfg, mm, h, w):
    """One pre-norm block on ``h`` [B, T, D]; ``w`` holds this layer's
    float32 weights."""
    import jax
    import jax.numpy as jnp

    B, T, D = h.shape
    H = cfg["n_head"]
    x = rmsnorm(h, w["ln1_g"])
    q, k, v = (mm("btd,de->bte", x, w[n]).reshape(B, T, H, D // H)
               for n in ("w_q", "w_k", "w_v"))
    s = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(D // H)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    a = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v).reshape(B, T, D)
    h = h + mm("btd,de->bte", a, w["w_o"])
    x = rmsnorm(h, w["ln2_g"])
    return h + mm("btf,fd->btd",
                  jax.nn.gelu(mm("btd,df->btf", x, w["w_ff1"])), w["w_ff2"])


LAYER_LEAVES = ("ln1_g", "ln2_g", "w_q", "w_k", "w_v", "w_o", "w_ff1", "w_ff2")


def hidden(cfg, mm, p, tokens):
    """Final-norm hidden states [B, T, D] of float32 params ``p``."""
    import jax

    h = p["embed"][tokens] + p["pos"][: tokens.shape[1]]
    layer = jax.checkpoint(functools.partial(block, cfg, mm))   # fits
    for i in range(cfg["n_layer"]):
        h = layer(h, {n: p[n][i] for n in LAYER_LEAVES})
    return rmsnorm(h, p["ln_f_g"])


def loss_fn(cfg, mm, p, tokens):
    """Mean next-token cross-entropy; the head is the tied embedding."""
    import jax
    import jax.numpy as jnp

    logits = mm("btd,vd->btv", hidden(cfg, mm, p, tokens), p["embed"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def leaf_norms(tree) -> list:
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(tree[n].astype(jnp.float32))))
            for n in LEAVES]


def follow(cfg: dict, seed31: int, batches, steps: int, compute: str = "",
           fault: str = "") -> dict:
    """Train ``steps`` steps on ``batches[i]``. Returns each step's loss,
    per leaf the norm of the first gradient, and per leaf the norm of the
    parameters' change after the last step.

    ``fault`` plants a fault in the reference put in the program's
    place: ``half_batch`` takes loss and gradient over the first half of
    each batch's rows."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    mm = _ops(compute)
    lr, beta = cfg["learning_rate"], cfg["momentum"]

    @jax.jit
    def step(p, m, tokens):
        if fault == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        up = {n: p[n].astype(jnp.float32) for n in LEAVES}
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, mm, q, tokens))(up)
        m = {n: beta * m[n] + g[n].astype(dtype) for n in LEAVES}
        p = {n: p[n] - lr * m[n] for n in LEAVES}
        return p, m, loss, leaf_norms(g)

    p0 = init_params(cfg, seed31, dtype)
    p, m = p0, {n: jnp.zeros_like(p0[n]) for n in LEAVES}
    out = {"loss": [], "first": None, "last": None}
    for i in range(steps):
        p, m, loss, gn = step(p, m, batches[i])
        out["loss"].append(float(loss))
        if i == 0:
            out["first"] = [float(x) for x in gn]
    diff = jax.jit(lambda a, b: leaf_norms(
        {n: a[n].astype(jnp.float32) - b[n].astype(jnp.float32)
         for n in LEAVES}))
    out["last"] = [float(x) for x in diff(p, p0)]
    return out


# -- serving: what the served tokens are held against -------------------------
def token_gaps(cfg: dict, seed31: int, sequences, prompt_lens,
               compute: str = "", block_rows: int = 8) -> list:
    """For each sequence (prompt then served tokens) one full causal
    forward pass; returns per sequence the widest gap by which a served
    token's logit lies below the best logit at its position (0 where
    the served token is the reference's own greedy choice). With
    ``compute`` set, the gap of the token that the lower precision puts
    first instead: the control, which need not decode."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["dtype"])
    exact, low = _ops(""), _ops(compute)
    p = {n: v.astype(jnp.float32)
         for n, v in init_params(cfg, seed31, dtype).items()}

    @jax.jit
    def gaps(p, tokens, first, last):
        h = hidden(cfg, exact, p, tokens)
        logits = exact("btd,vd->btv", h, p["embed"])[:, :-1]
        if compute:
            served = jnp.argmax(low("btd,vd->btv", hidden(cfg, low, p, tokens),
                                    p["embed"])[:, :-1], -1)
        else:
            served = tokens[:, 1:]
        took = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        at = jnp.arange(tokens.shape[1] - 1)[None, :]
        inside = (at >= first[:, None] - 1) & (at < last[:, None] - 1)
        return jnp.max(jnp.where(inside, logits.max(-1) - took, 0.0), -1)

    T = cfg["n_positions"]
    out = []
    for i in range(0, len(sequences), block_rows):
        rows = sequences[i:i + block_rows]
        toks = np.zeros((block_rows, T), np.int32)
        for j, s in enumerate(rows):
            toks[j, :len(s)] = s
        first = np.zeros(block_rows, np.int32)
        last = np.zeros(block_rows, np.int32)
        first[:len(rows)] = prompt_lens[i:i + block_rows]
        last[:len(rows)] = [len(s) for s in rows]
        out += [float(x) for x in gaps(p, jnp.asarray(toks), jnp.asarray(first),
                                       jnp.asarray(last))][:len(rows)]
    return out
