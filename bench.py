"""Benchmark: WordEmbedding training throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"negatives", "platform", "device_kind", "device_count"}. Needs a TPU:
on any other backend it says so on stderr, prints no record and exits 1.

Metric: word2vec skip-gram negative-sampling training pairs/sec at the
reference's NAMED configuration shape — text8: ~71k vocabulary, 200-dim
embeddings (BASELINE.json config 2; the corpus itself is synthesised with a
zipf unigram law because this environment has no network egress, but vocab
size, dimensionality, window, negatives and subsampling all match).
Negative draws are group-shared at G=64 (round 4: the 71k-vocab
real-scale probe — `tools/embedding_quality.py --realscale`, the frozen
bench config with planted clusters — holds full parity at every probed
G through 256 in aggregate AND in every zipf frequency band; the
default is capped at G=64 anyway because the <1% loss guard binds
first: final training loss drifts monotonically off the exact-draw
semantics (+0.8% at G=64, +1.4% at G=128 — the planted-cluster bar
saturates and stops discriminating, so a loss guard caps what the bar
cannot), while device-rate gains past G=64 are +2-3% per doubling
(xprof spans: 10.73M on-device at G=64, 11.06M at G=128) — not worth
double the loss drift. The r3 G=4 cap came from a
deliberately-harsh 332-word probe whose within-group negative
correlation is ~200x denser than text8's. Exact per-pair draws remain
one flag away, `-shared_negatives=0`.) Updates use the capped row-mean
stabiliser
(quality parity in the same doc) because raw summed updates DIVERGE at
64k batch on a zipf corpus — see the auto rule in apps/wordembedding.py.
Config provenance/freeze: BASELINE.md "bench.py config provenance".

``vs_baseline`` is the ratio against 1.0M pairs/sec, the ballpark of the
reference C++ implementation's per-host throughput on its published hardware
(the reference logs the metric but publishes no numbers — BASELINE.md).
The per-op roofline breakdown behind this number is in README.md
("Performance" section) and reproducible with tools/w2v_profile.py.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

_BASELINE_PAIRS_PER_SEC = 1_000_000.0

# text8 shape (reference named config): 71,291-word vocab, 200 dims
_VOCAB = 71291
_DIM = 200


def make_corpus(path: str, n_words: int = 4_000_000, vocab: int = _VOCAB,
                seed: int = 5) -> None:
    rng = np.random.default_rng(seed)
    # zipf-ish unigram distribution over a closed vocab; one guaranteed
    # occurrence of every word so the dictionary reaches the full text8
    # vocabulary size
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    words = rng.choice(vocab, size=n_words, p=probs)
    words[:vocab] = rng.permutation(vocab)
    with open(path, "w") as f:
        for i in range(0, n_words, 1000):
            f.write(" ".join(f"w{w}" for w in words[i:i + 1000]) + "\n")


def main() -> int:
    import multiverso_tpu as mv
    from multiverso_tpu.apps.wordembedding import (Dictionary, encode_corpus,
                                                   subsample_probs)
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    # default = G=64 group-shared draws (parity-proven at the real-scale
    # probe in aggregate and per frequency band; capped at 64 by the
    # loss guard + measured throughput saturation —
    # docs/EMBEDDING_QUALITY.md real-scale section); `-shared_negatives=0`
    # restores exact per-pair reference semantics (parsed by the
    # framework's own flag registry, like every other option).
    mv.define_int("shared_negatives", 64,
                  "share each K-negative draw across G consecutive pairs")

    rest = mv.init(["bench", "-log_level=error"] + sys.argv[1:])
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        # a throughput number from another backend is not this metric
        print(f"bench: needs a TPU, found platform {device.platform!r}; "
              "no record printed", file=sys.stderr)
        mv.shutdown()
        return 1
    # bench has no app-layer flags beyond the registry: anything left over
    # is a typo or a bad value ('-oversample=2' once silently measured the
    # default config). Distinguish the two — a known key lands here when
    # its value failed coercion.
    leftover = [t for t in rest if t != "bench"]
    if leftover:
        from multiverso_tpu import config as _cfg

        for tok in leftover:
            key = tok.lstrip("-").partition("=")[0]
            kind = ("bad value for flag" if _cfg.registry().known(key)
                    else "unknown flag")
            print(f"bench: {kind}: {tok}", file=sys.stderr)
        mv.shutdown()
        return 2
    corpus = os.path.join(tempfile.gettempdir(), "mv_bench_corpus_text8.txt")
    if not os.path.exists(corpus):
        make_corpus(corpus)
    shared_neg = mv.get_flag("shared_negatives")
    dictionary = Dictionary.build(corpus, min_count=1)
    # TPU-native settings: bf16 embedding tables (f32 score/grad
    # accumulation in the step), 2.5x candidate oversampling so the
    # window/subsample rejection tests don't waste gather/scatter slots,
    # pre-drawn negative pool (contiguous-slice draws instead of random
    # gathers). row_mean (capped, cap=8) is ON: at 64k batch on a zipf
    # corpus the head words collect thousands of colliding pair grads per
    # step and raw summed updates diverge (NaN) — the reference's
    # sequential loop self-limits via sigmoid saturation; the cap plays
    # that role and measures quality parity (docs/EMBEDDING_QUALITY.md;
    # the static expected-count form scores identically and skips the
    # per-step counts scatter). Raw summed semantics remain available
    # (and stable) at small batch.
    cfg = Word2VecConfig(vocab_size=dictionary.vocab_size,
                         embedding_size=_DIM,
                         window=5, negative=5, init_lr=0.025,
                         batch_size=65536,
                         oversample=2.5, neg_pool_size=1 << 22,
                         row_mean_updates=True, row_mean_static=True,
                         shared_negatives=shared_neg)
    import jax.numpy as jnp
    w_in = mv.create_table("matrix", dictionary.vocab_size, _DIM,
                           init_value="random", dtype=jnp.bfloat16)
    w_out = mv.create_table("matrix", dictionary.vocab_size, _DIM,
                            dtype=jnp.bfloat16)
    model = Word2Vec(cfg, w_in, w_out,
                     counts=np.asarray(dictionary.counts, np.float64))
    model.total_words = 10 ** 9

    # device-resident corpus: upload once, sample+train on device
    ids, sent_ids = encode_corpus(corpus, dictionary)
    discard = subsample_probs(np.asarray(dictionary.counts, np.float64),
                              1e-3).astype(np.float32)
    model.load_corpus_chunk(ids, sent_ids, discard)

    steps_per_call = 25
    loss, count = model.train_device_steps(steps_per_call)  # compile
    float(loss)

    # 20 x 25-step dispatches — FROZEN since r3 for cross-round
    # comparability (BASELINE.md "bench.py config provenance").
    iters = 20
    counts = []
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, count = model.train_device_steps(steps_per_call)
        counts.append(count)
    pairs = float(np.sum([float(c) for c in counts]))  # blocks on final
    elapsed = time.perf_counter() - t0
    mv.shutdown()

    value = pairs / elapsed
    # the negative-draw mode rides in the output line so every recorded
    # number is self-describing: G>1 group-shares draws (an algorithmic
    # relaxation over the reference's exact per-pair semantics — disclosed
    # in BASELINE.md, parity-gated in docs/EMBEDDING_QUALITY.md)
    record = {
        "metric": "word2vec_train_pairs_per_sec",
        "value": round(value, 1),
        "unit": "pairs/sec",
        "vs_baseline": round(value / _BASELINE_PAIRS_PER_SEC, 4),
        "negatives": ("exact" if shared_neg in (0, 1)
                      else f"group-shared G={shared_neg}"),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
