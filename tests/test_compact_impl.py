"""The candidate pack of the device-corpus sampler (``pack_survivors``).

The sampler over-draws M = oversample*B candidates and packs the
survivors into the B training slots: slot b holds the b-th survivor in
corpus order, the slots past the survivors are zero. The trainer packs
by one sort; this file holds it to the plain packing, written out here
in numpy and (the prefix-rank scatter the trainer used to run) in jax:
the same rows in the same slots, so the same seed and corpus give the
same losses and the same final tables, bit for bit.
"""

from __future__ import annotations

import numpy as np
import pytest


def _plain_pack(ok, n_slots, *arrays):
    """Each survivor scattered to its prefix-count rank."""
    import jax.numpy as jnp

    rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
    dest = jnp.where(ok & (rank < n_slots), rank, n_slots)
    packed = tuple(
        jnp.zeros((n_slots,) + a.shape[1:], a.dtype).at[dest].set(
            a, mode="drop")
        for a in arrays)
    return packed + (jnp.arange(n_slots) < ok.sum(),)


@pytest.mark.parametrize("survivors", ["none", "few", "exactly_full",
                                       "overflow", "all"])
def test_pack_survivors_against_numpy(survivors):
    """1-D id arrays and a 2-D mask (CBOW's), for every filling of the
    slots: fewer survivors than slots, as many, and more."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.word2vec import pack_survivors

    M, B = 200, 64
    rng = np.random.default_rng(4)
    n_ok = {"none": 0, "few": 23, "exactly_full": B, "overflow": 150,
            "all": M}[survivors]
    ok = np.zeros(M, bool)
    ok[rng.permutation(M)[:n_ok]] = True
    ids = rng.integers(1, 1000, M).astype(np.int32)
    other = rng.integers(1, 1000, M).astype(np.int32)
    mask = rng.random((M, 6)) < 0.5

    got = jax.jit(pack_survivors, static_argnums=1)(
        jnp.asarray(ok), B, jnp.asarray(ids), jnp.asarray(other),
        jnp.asarray(mask))

    kept = min(n_ok, B)
    for g, a in zip(got, (ids, other, mask)):
        want = np.zeros((B,) + a.shape[1:], a.dtype)
        want[:kept] = a[ok][:B]
        np.testing.assert_array_equal(np.asarray(g), want)
    np.testing.assert_array_equal(np.asarray(got[-1]), np.arange(B) < kept)
    for g, w in zip(got, _plain_pack(jnp.asarray(ok), B, jnp.asarray(ids),
                                     jnp.asarray(other), jnp.asarray(mask))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _run(mv, tag: str, cbow: bool):
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    rng = np.random.default_rng(3)
    vocab, dim, B = 400, 16, 4096
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    counts = np.maximum(probs * 1e6, 5)
    ids = rng.choice(vocab, size=60_000, p=probs).astype(np.int32)
    sents = (np.arange(ids.size) // 150).astype(np.int32)

    cfg = Word2VecConfig(vocab_size=vocab, embedding_size=dim,
                         negative=3, batch_size=B, seed=11,
                         oversample=2.0, cbow=cbow)
    w_in = mv.create_table("matrix", vocab, dim, init_value="random",
                           seed=9, name=f"ci_in_{tag}_{cbow}")
    w_out = mv.create_table("matrix", vocab, dim,
                            name=f"ci_out_{tag}_{cbow}")
    m = Word2Vec(cfg, w_in, w_out, counts=counts)
    m.load_corpus_chunk(ids, sents, np.zeros(vocab, np.float32))
    losses = []
    for _ in range(4):
        loss, count = m.train_device_steps(2)
        losses.append(float(loss))
    assert float(count) > 0
    return losses, np.asarray(w_in.get()), np.asarray(w_out.get())


@pytest.mark.parametrize("cbow", [False, True],
                         ids=["skipgram", "cbow"])
def test_sorted_and_plain_pack_train_identically(mv_session, monkeypatch,
                                                 cbow):
    # cbow additionally packs a 2-D ok mask and re-masks with ex_packed:
    # the wide branch of the pack
    from multiverso_tpu.models import word2vec

    l_s, in_s, out_s = _run(mv_session, "sort", cbow)
    monkeypatch.setattr(word2vec, "pack_survivors", _plain_pack)
    l_p, in_p, out_p = _run(mv_session, "plain", cbow)
    assert np.allclose(l_s, l_p, rtol=0, atol=0), (l_s, l_p)
    assert np.array_equal(in_s, in_p)
    assert np.array_equal(out_s, out_p)
