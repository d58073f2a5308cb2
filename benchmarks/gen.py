"""The one general generator: inputs and weights from ``--seed``.

A traffic mix is a data file of parameters under ``benchmarks/traffic/``;
this module turns those parameters and a seed into inputs, on the device
where they are large. The same seed gives the same inputs; the drivers
and the references both call it, and nothing here imports the program.
"""

from __future__ import annotations

import functools
import math

import numpy as np


def _key(seed: int, stream: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), stream)


# -- word2vec -------------------------------------------------------------------
def w2v_counts(vocab: int, total_words: float) -> np.ndarray:
    """Expected corpus count of every word under the law ``w2v_corpus``
    draws from: P(rank r) = ln((r+1)/r) / ln(V+1), r = 1..V, a zipf law
    of exponent 1 (its continuous form, so the inverse CDF is closed)."""
    r = np.arange(1, vocab + 1, dtype=np.float64)
    return total_words * np.log1p(1.0 / r) / math.log(vocab + 1.0)


def w2v_corpus(seed: int, n_words: int, vocab: int, sentence_words: int):
    """``(ids, sentence ids)`` of an ``n_words`` chunk, int32, on the
    device: inverse CDF of the law above, rank = floor((V+1)**u), and
    sentences of ``sentence_words`` words."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def make(key, n, v, s):
        u = jax.random.uniform(key, (n,), jnp.float32)
        rank = jnp.floor(jnp.exp(u * math.log(v + 1.0))).astype(jnp.int32)
        return (jnp.clip(rank, 1, v) - 1,
                jnp.arange(n, dtype=jnp.int32) // s)

    return make(_key(seed, 1), int(n_words), int(vocab), int(sentence_words))


def w2v_init_table(seed: int, shape, dtype):
    """``(U[0,1) - 0.5) / dim``: upstream word2vec's input-table law."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(key, shape, dtype):
        u = jax.random.uniform(key, shape, jnp.float32)
        return ((u - 0.5) / shape[1]).astype(dtype)

    return make(_key(seed, 2), tuple(shape), jnp.dtype(dtype))


# -- language model --------------------------------------------------------------
def lm_batches(seed: int, n: int, batch: int, seq: int, vocab: int):
    """``n`` batches of uniform token ids ``[n, batch, seq]``, int32, on
    the device. Every row differs (a repeat has probability ~0)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(key, shape, vocab):
        return jax.random.randint(key, shape, 0, vocab, jnp.int32)

    return make(_key(seed, 3), (int(n), int(batch), int(seq)), int(vocab))


def serve_requests(seed: int, n: int, vocab: int, prompt_min: int,
                   prompt_max: int, new_min: int, new_max: int,
                   cycle: int):
    """``n`` requests ``(prompt ids, max_new)``: prompt lengths
    log-uniform in ``[prompt_min, prompt_max]``, answer lengths uniform
    in ``[new_min, new_max]``, ids uniform over the vocabulary and fresh
    for every request, so no two prompts share a prefix but by chance.

    The lengths are one cycle of ``cycle`` pairs, the same for every
    seed, laid out so that any stretch of consecutive requests carries
    nearly the whole of both laws: request ``j`` of the cycle takes the
    answer length at the quantile of ``j``'s bit-reversed index (van der
    Corput) and the prompt length at the quantile ``frac(j * 0.618...)``
    (the golden-ratio sequence). The seed draws the ids and where in the
    cycle the first request stands: every seed sends the same sizes in
    another order (a rotation), so it changes the ids and the phase, not
    the work. Lengths drawn at random moved the requests a window
    completes by 2.5% from seed to seed (read on the v5e, PR 24): which
    answer lengths stand near the window's two edges decides it."""
    j = np.arange(cycle)
    bits = max(1, int(cycle - 1).bit_length())
    q_new = np.array([int(format(int(i), f"0{bits}b")[::-1], 2)
                      for i in j]) / float(2 ** bits)
    q_prompt = (j * 0.6180339887498949) % 1.0
    lo, hi = math.log(prompt_min), math.log(prompt_max + 1)
    plen = np.clip(np.floor(np.exp(lo + q_prompt * (hi - lo))).astype(int),
                   prompt_min, prompt_max)
    nnew = new_min + np.floor(q_new * (new_max - new_min + 1)).astype(int)
    rng = np.random.default_rng(seed)
    order = (int(rng.integers(cycle)) + np.arange(n)) % cycle
    return [(rng.integers(0, vocab, int(plen[i])).astype(np.int32),
             int(nnew[i])) for i in order]
