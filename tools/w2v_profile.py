"""Word2vec hot-path profiling on the real chip (VERDICT r1 item 4).

Measures the device-resident training pipeline at the text8-shaped config
(71k vocab, 200-dim) and ablates its stages so the throughput ceiling is a
measured fact, not a guess:

* full fused step (sample + train) — the bench.py number;
* train-only on a fixed batch (no sampler) — isolates the gather/scatter
  + MXU objective work; the printed "sampler overhead" is the
  full-minus-train residual (sampling + the dispatch/fusion differences
  between the two programs);
* bytes-per-pair roofline vs the chip's HBM bandwidth.

Optionally dumps an xprof trace (``--trace DIR``) via
``dashboard.profile_trace`` for op-level inspection.

Usage: python tools/w2v_profile.py [--dim 200] [--vocab 71291] [--trace DIR]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def timed(fn, iters=10):
    import jax

    jax.block_until_ready(fn())       # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=71291)   # text8 vocab
    ap.add_argument("--dim", type=int, default=200)       # text8 config dim
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--negative", type=int, default=5)
    ap.add_argument("--bf16", type=int, default=1)
    ap.add_argument("--oversample", type=float, default=2.5)
    ap.add_argument("--row_mean", type=int, default=1)
    ap.add_argument("--static", type=int, default=0,
                    help="row_mean_static (the shipped bench stabiliser)")
    ap.add_argument("--shared", type=int, default=0,
                    help="shared_negatives group size G (bench default 64)")
    ap.add_argument("--trace", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    mv.init(["profile", "-log_level=error"])
    vocab, D, B, S, K = (args.vocab, args.dim, args.batch, args.steps,
                         args.negative)
    rng = np.random.default_rng(0)
    # zipf-ish counts like a real corpus
    counts = (1.0 / np.arange(1, vocab + 1)) ** 1.0
    counts = np.maximum(counts / counts.min(), 5).astype(np.float64)

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    cfg = Word2VecConfig(vocab_size=vocab, embedding_size=D, window=5,
                         negative=K, batch_size=B, oversample=args.oversample,
                         neg_pool_size=1 << 22,
                         row_mean_updates=bool(args.row_mean),
                         row_mean_static=bool(args.static),
                         shared_negatives=args.shared)
    w_in = mv.create_table("matrix", vocab, D, init_value="random",
                           dtype=dtype, name="w_in")
    w_out = mv.create_table("matrix", vocab, D, dtype=dtype, name="w_out")
    model = Word2Vec(cfg, w_in, w_out, counts=counts)
    model.total_words = 10 ** 9

    # synthetic corpus in HBM: zipf draws, sentence breaks every ~1k
    n_tok = 2_000_000
    probs = counts / counts.sum()
    ids = rng.choice(vocab, size=n_tok, p=probs).astype(np.int32)
    sent = (np.arange(n_tok) // 1000).astype(np.int32)
    model.load_corpus_chunk(ids, sent, np.zeros(vocab, np.float32))

    # ---- full fused pipeline -------------------------------------------
    def full():
        loss, count = model.train_device_steps(S)
        return loss

    t_full = timed(full)
    pairs = S * B
    full_rate = pairs / t_full
    print(f"full fused: {t_full*1e3:8.2f} ms / {S} steps  "
          f"-> {full_rate/1e6:7.2f}M pairs/s", flush=True)

    # ---- train-only: fixed batches through the multi-step scan ---------
    centers = jnp.asarray(rng.choice(vocab, (S, B), p=probs), jnp.int32)
    contexts = jnp.asarray(rng.choice(vocab, (S, B), p=probs), jnp.int32)
    mask = jnp.ones((S, B), jnp.float32)

    def train_only():
        return model.train_batches(centers, contexts, mask)

    t_train = timed(train_only)
    print(f"train-only: {t_train*1e3:8.2f} ms / {S} steps  "
          f"-> {pairs/t_train/1e6:7.2f}M pairs/s", flush=True)
    print(f"sampler overhead: {(t_full-t_train)/t_full*100:5.1f}% of full",
          flush=True)

    # ---- roofline -------------------------------------------------------
    itemsize = np.dtype(np.float32).itemsize // 2 if args.bf16 else 4
    # per pair: in-row gather + scatter-add (read+write), (1+K/G) out rows
    # gather + scatter-add (G pairs share one K-negative draw);
    # scatter-add = read + write of the row
    G = max(args.shared, 1)
    rows_moved = (1 + 2) + (1 + K / G) * (1 + 2)
    bytes_per_pair = rows_moved * D * itemsize
    HBM = 819e9   # v5e ~819 GB/s
    bound = HBM / bytes_per_pair
    print(f"roofline: {bytes_per_pair/1e3:.2f} KB/pair -> HBM bound "
          f"{bound/1e6:.1f}M pairs/s; full = {full_rate/bound*100:.1f}% "
          f"of bound", flush=True)

    if args.trace:
        from multiverso_tpu.dashboard import profile_trace

        with profile_trace(args.trace):
            for _ in range(3):
                model.train_device_steps(S)
            jax.block_until_ready(model.input_table._data)
        print(f"trace -> {args.trace}", flush=True)

    mv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
