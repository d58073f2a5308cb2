"""Plain reference for ``w2v-news3m-d300``: skip-gram negative sampling
with the device sampler, in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
the corpus and the initial tables come from ``benchmarks/gen.py`` and
the seed, and the alias tables, the negative pool, the subsampling law
and the row scales are built here from the word counts. What it shares
with the trainer is the published arithmetic and ``jax.random``: it
makes the same draws from the same key in the same order, so it trains
the very pairs the trainer trained, and the two differ by rounding.

What the configuration states is kept: tables hold ``table_dtype``
(bfloat16), every update is rounded to it before it is added, and
scores, gradients and the loss are float32 (products at
``Precision.HIGHEST``). ``store`` puts another type in the tables'
place: an 8-bit float, the control.

Departures from upstream word2vec, all the trainer's own and stated in
the configuration: a batch's gradients are taken at the pre-step
tables and summed per row; each K-negative draw is shared by
``shared_negatives`` consecutive pairs; a row's update is scaled by
``min(E, cap) / max(E, 1)`` with E its expected hits per step.
"""

from __future__ import annotations

import functools

import numpy as np


# -- the laws, from the counts ---------------------------------------------------
def discard_probs(counts: np.ndarray, sample: float) -> np.ndarray:
    """word2vec's sub-sampling: keep a word of frequency f with
    probability (sqrt(f/t) + 1) * t / f."""
    freq = counts / counts.sum()
    keep = (np.sqrt(freq / sample) + 1) * (sample / np.maximum(freq, 1e-12))
    return np.clip(1.0 - keep, 0.0, 1.0)


def alias_tables(counts: np.ndarray, power: float = 0.75):
    """Walker's alias method for the unigram**0.75 law. The order in
    which small and large buckets are paired decides the tables, and
    with them every draw, so it is the stack order the trainer
    documents: both stacks filled in rank order, popped from the end."""
    probs = counts.astype(np.float64) ** power
    probs /= probs.sum()
    n = probs.shape[0]
    scaled = probs * n
    alias = np.zeros(n, np.int32)
    thresh = np.ones(n, np.float32)
    is_small = scaled < 1.0
    small = list(np.nonzero(is_small)[0])
    large = list(np.nonzero(~is_small)[0])
    while small and large:
        s, l = small.pop(), large.pop()
        thresh[s] = scaled[s]
        alias[s] = l
        scaled[l] = scaled[l] - (1.0 - scaled[s])
        (small if scaled[l] < 1.0 else large).append(l)
    for i in large + small:
        thresh[i] = 1.0
        alias[i] = i
    return thresh, alias


def negative_pool(thresh, alias, size: int, seed: int) -> np.ndarray:
    """``size`` pre-drawn negatives (the 1e8-slot table of upstream's
    sampler, drawn once): uniform bucket, then bucket or its alias."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, thresh.shape[0], size).astype(np.int32)
    u = rng.random(size).astype(np.float32)
    return np.where(u < thresh[idx], idx, alias[idx]).astype(np.int32)


def row_scales(counts, discard, batch: int, negative: int, cap: float):
    """Expected hits per step and row -> ``min(E, cap) / max(E, 1)``,
    for the input table (centres) and the output table (contexts and
    negatives)."""
    eff = counts * np.clip(1.0 - discard, 0.0, 1.0)
    p_eff = eff / eff.sum()
    p_neg = counts ** 0.75 / np.sum(counts ** 0.75)

    def scale(e):
        c = np.maximum(e, 1.0)
        return (np.minimum(c, max(cap, 1.0)) / c).astype(np.float32)

    return scale(batch * p_eff), scale(batch * p_eff + batch * negative * p_neg)


def learning_rate(cfg: dict, dispatch: int, global_batch: int) -> float:
    """Linear decay over corpus words; a dispatch is booked as
    ``steps * batch / 2`` examples of ``window + 1`` pairs a word."""
    words = dispatch * cfg["steps_per_dispatch"] * global_batch * 0.5 \
        / (cfg["window"] + 1)
    frac = 1.0 - words / (cfg["total_words"] + 1)
    return cfg["init_lr"] * max(frac, 1e-4)


# -- one dispatch -----------------------------------------------------------------
def _quantizer(store: str):
    """Values as the 8-bit float ``store`` holds them, in a bfloat16
    container (which holds every such value exactly, and which the
    chip's scatter takes)."""
    import jax.numpy as jnp

    dtype = jnp.dtype(store)
    return lambda x: x.astype(dtype).astype(jnp.bfloat16)


def make_dispatch(cfg: dict, n_words: int, store: str, fault: str = ""):
    """The jitted function of one dispatch: ``steps_per_dispatch``
    sample-and-train steps of one worker on its tables.

    ``fault`` plants one of the faults the check has to catch, in the
    reference put in the program's place: ``half_batch`` trains only
    the first half of every batch and averages the loss over it."""
    import jax
    import jax.numpy as jnp

    S, W, K = cfg["steps_per_dispatch"], cfg["window"], cfg["negative"]
    B, G, D = cfg["batch_size_per_worker"], cfg["shared_negatives"], \
        cfg["embedding_size"]
    M = max(B, int(round(B * cfg["oversample"])))
    M = min(M, n_words - 2 * W)
    R = B // G
    native = store in ("bfloat16", "float32")
    quant = None if native else _quantizer(store)
    einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def add_rows(table, rows, upd):
        if native:                          # rounded to the table's type,
            return table.at[rows].add(upd.astype(table.dtype))  # then added
        # the rows it touched are rounded again; the rest hold ``store``
        # values already (a pass over the whole table does not fit)
        table = table.at[rows].add(quant(upd))
        return table.at[rows].set(quant(table[rows]))

    def step(tables, xs, corpus, sents, disc, scale_in, scale_out, lr):
        w_in, w_out = tables
        start, offset, u_centre, u_ctx, negs = xs
        n = corpus.shape[0]
        pos = (start + jnp.arange(M, dtype=jnp.int32)) % n
        ctx_pos = (pos + offset) % n
        c_all, x_all = corpus[pos], corpus[ctx_pos]
        ok = (sents[pos] == sents[ctx_pos]) \
            & (u_centre >= disc[c_all]) & (u_ctx >= disc[x_all])
        n_valid = jnp.minimum(ok.sum(), B)
        (first,) = jnp.nonzero(ok, size=B, fill_value=0)
        valid = jnp.arange(B) < n_valid
        centres = jnp.where(valid, c_all[first], 0)
        contexts = jnp.where(valid, x_all[first], 0)
        mask = valid.astype(jnp.float32)
        if fault == "half_batch":
            mask = mask * (jnp.arange(B) < B // 2)
        flat = negs.reshape(-1)

        h = w_in[centres].astype(jnp.float32)
        u_pos = w_out[contexts].astype(jnp.float32)
        u_neg = w_out[negs].astype(jnp.float32)                 # [R, K, D]
        hg, mg = h.reshape(R, G, D), mask.reshape(R, G)
        s_pos = jnp.clip(jnp.sum(h * u_pos, -1), -30.0, 30.0)
        s_neg = jnp.clip(einsum("gbd,gkd->gbk", hg, u_neg), -30.0, 30.0)
        g_pos = (jax.nn.sigmoid(s_pos) - 1.0) * mask
        g_neg = jax.nn.sigmoid(s_neg) * mg[:, :, None]
        loss = (jnp.sum((jax.nn.softplus(s_pos) - s_pos) * mask)
                + jnp.sum(jax.nn.softplus(s_neg) * mg[:, :, None])) \
            / jnp.maximum(mask.sum(), 1)
        d_h = g_pos[:, None] * u_pos \
            + einsum("gbk,gkd->gbd", g_neg, u_neg).reshape(B, D)
        d_pos = g_pos[:, None] * h
        d_neg = einsum("gbk,gbd->gkd", g_neg, hg).reshape(-1, D)

        w_in = add_rows(w_in, centres, -lr * scale_in[centres][:, None] * d_h)
        w_out = add_rows(w_out, contexts,
                         -lr * scale_out[contexts][:, None] * d_pos)
        w_out = add_rows(w_out, flat, -lr * scale_out[flat][:, None] * d_neg)
        return (w_in, w_out), (loss, mask.sum())

    # the tables are donated: a second pair beside them does not fit
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def dispatch(w_in, w_out, key, start0, lr, corpus, sents, disc, pool,
                 scale_in, scale_out):
        n = corpus.shape[0]
        key, k1, k2, k3, k4, k5 = jax.random.split(key, 6)
        shrink = jax.random.randint(k1, (S, M), 1, W + 1)
        reach = jnp.minimum(jax.random.randint(k2, (S, M), 1, W + 1), shrink)
        offset = jnp.where(jax.random.bernoulli(k3, 0.5, (S, M)), reach, -reach)
        u_centre = jax.random.uniform(k4, (S, M))
        u_ctx = jax.random.uniform(k5, (S, M))
        key, kn = jax.random.split(key)
        at = jax.random.randint(kn, (), 0, pool.shape[0] - S * R * K + 1)
        negs = jax.lax.dynamic_slice(pool, (at,), (S * R * K,)).reshape(S, R, K)
        starts = (start0 + jnp.arange(S, dtype=jnp.int32) * M) % n
        (w_in, w_out), (losses, counts) = jax.lax.scan(
            lambda t, xs: step(t, xs, corpus, sents, disc, scale_in,
                               scale_out, lr),
            (w_in, w_out), (starts, offset, u_centre, u_ctx, negs))
        return w_in, w_out, losses.mean(), counts.sum(), key

    return dispatch, M


def _norm(x):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def follow(cfg: dict, seed: int, dispatches: int, workers: int = 1,
           store: str = "", fault: str = "") -> dict:
    """Train ``dispatches`` dispatches from the seed. Returns per
    dispatch the loss and the pairs counted, and the norms of each
    table's change after the first dispatch and after the last.

    ``workers`` > 1 is the one-worker oracle of ``dp_sync="dispatch"``:
    each worker trains a copy of the tables on its own arc of the chunk
    with its own key, and the dispatch ends in the sum of their changes,
    taken in the tables' type."""
    import jax
    import jax.numpy as jnp

    from benchmarks import gen

    store = store or cfg["table_dtype"]
    native = store in ("bfloat16", "float32")
    V, D = cfg["vocab_size"], cfg["embedding_size"]
    B = cfg["batch_size_per_worker"]
    n = cfg["corpus_words"]
    counts = gen.w2v_counts(V, cfg["total_words"])
    discard = discard_probs(counts, cfg["sample"]).astype(np.float32)
    thresh, alias = alias_tables(counts)
    S, K, G = cfg["steps_per_dispatch"], cfg["negative"], \
        cfg["shared_negatives"]
    pool = jnp.asarray(negative_pool(
        thresh, alias, max(cfg["neg_pool_size"], 2 * S * (B // G) * K),
        cfg["trainer_seed"] + 1))
    scale_in, scale_out = (jnp.asarray(s) for s in row_scales(
        counts, discard.astype(np.float64), B, K, cfg["row_update_cap"]))
    corpus, sents = gen.w2v_corpus(seed, n, V, cfg["sentence_words"])
    disc = jnp.asarray(discard)
    dispatch, M = make_dispatch(cfg, n, store, fault)

    held = jnp.dtype(store) if native else jnp.bfloat16

    def initial():                  # drawn again where it is compared
        w = gen.w2v_init_table(seed, (V, D), cfg["table_dtype"]).astype(held)
        return w if native else _quantizer(store)(w)

    w_in, w_out = initial(), jnp.zeros((V, D), held)
    change = jax.jit(lambda a, a0, b: (_norm(a.astype(jnp.float32)
                                             - a0.astype(jnp.float32)),
                                       _norm(b)))
    # Several workers: the first chip trains one worker's copy at a time;
    # the tables as the dispatch found them and the sum of the workers'
    # changes wait on another chip where there is one (on one chip they do
    # not fit beside the copy in training and the step's temporaries).
    minus = jax.jit(lambda new, old: new - old, donate_argnums=(0,))
    plus = jax.jit(lambda x, y: x + y, donate_argnums=(0,))
    home, spare = jax.devices()[0], jax.devices()[-1]

    def to(x, device):              # a copy of ``x`` on ``device``
        return jnp.copy(x) if device in x.devices() \
            else jax.device_put(x, device)
    key = jax.random.PRNGKey(cfg["trainer_seed"])
    out = {"loss": [], "pairs": [], "first": None, "last": None}
    pos = 0
    for d in range(dispatches):
        lr = jnp.float32(learning_rate(cfg, d, B * workers))
        if workers == 1:
            w_in, w_out, loss, pairs, key = dispatch(
                w_in, w_out, key, jnp.int32(pos), lr, corpus, sents, disc,
                pool, scale_in, scale_out)
        else:
            old = [to(w_in, spare), to(w_out, spare)]
            del w_in, w_out
            total, loss, pairs = None, 0.0, 0.0
            for w in range(workers):
                *new, l, c, _ = dispatch(
                    to(old[0], home), to(old[1], home),
                    jax.random.fold_in(key, w),
                    jnp.int32((pos + w * (n // workers)) % n), lr, corpus,
                    sents, disc, pool, scale_in, scale_out)
                delta = []
                while new:          # one table at a time
                    delta.append(minus(to(new.pop(0), spare), old[len(delta)]))
                total = delta if total is None else \
                    [plus(t, d) for t, d in zip(total, delta)]
                loss, pairs = loss + l / workers, pairs + c
            w_in, w_out = (to(plus(o, t), home) for o, t in zip(old, total))
            del old, total
            key = jax.random.split(key)[0]
        pos = (pos + S * M) % n
        out["loss"].append(float(loss))
        out["pairs"].append(float(pairs))
        if d in (0, dispatches - 1):
            norms = [float(x) for x in change(w_in, initial(), w_out)]
            out["first" if d == 0 else "last"] = norms
            if dispatches == 1:
                out["last"] = norms
    return out
