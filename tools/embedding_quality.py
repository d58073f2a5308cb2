"""Embedding-quality probe: batched update semantics vs the reference's.

VERDICT r1 item 5: the batched scatter path deviates from the reference's
sequential per-pair updates (``Applications/WordEmbedding/src/
wordembedding.cpp:120-168``) in two tunable ways — summed colliding grads
(row_mean off) or capped row-mean (row_mean on, ``row_update_cap``). This
tool quantifies what those semantics do to embedding QUALITY, not just loss:

* corpus: synthetic clustered language — K topic clusters; each sentence
  samples words from one cluster (plus shared stop-words), so ground truth
  is known: words of a cluster should embed near each other.
* probe: nearest-neighbor purity (fraction of content words whose cosine
  nearest neighbor is in their own cluster) and the within-minus-across
  cluster mean-cosine gap.

Runs a small sweep (reference-semantics small batch; summed and row-mean
variants at large batch; cap sweep) and writes a markdown table. The
numbers behind ``docs/EMBEDDING_QUALITY.md`` and the CLI's auto default.

Usage: python tools/embedding_quality.py [--quick] [--out docs/EMBEDDING_QUALITY.md]
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import tempfile

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def make_clustered_corpus(path: str, n_clusters: int = 8,
                          words_per_cluster: int = 40, n_stop: int = 12,
                          n_sentences: int 	= 30000, sent_len: int = 12,
                          stop_rate: float = 0.25, seed: int = 7):
    """Write the corpus; returns {word: cluster_id} (stop words -> -1)."""
    rng = random.Random(seed)
    clusters = [[f"c{k}w{i}" for i in range(words_per_cluster)]
                for k in range(n_clusters)]
    stops = [f"the{i}" for i in range(n_stop)]
    labels = {w: k for k, ws in enumerate(clusters) for w in ws}
    labels.update({w: -1 for w in stops})
    with open(path, "w") as f:
        for _ in range(n_sentences):
            k = rng.randrange(n_clusters)
            words = [rng.choice(stops) if rng.random() < stop_rate
                     else rng.choice(clusters[k]) for _ in range(sent_len)]
            f.write(" ".join(words) + "\n")
    return labels


def make_realscale_corpus(path: str, vocab: int = 71291,
                          n_clusters: int = 1000, cluster_size: int = 8,
                          n_tokens: int = 8_000_000, sent_len: int = 16,
                          topical_rate: float = 0.5, p_in: float = 0.6,
                          rank_lo: int = 100, rank_hi: int = 20000,
                          seed: int = 13):
    """text8-SCALE probe corpus (VERDICT r3 item 7): the full 71k zipf
    vocabulary of the bench corpus, with planted semantic clusters.

    The r3 probe's 332-word vocab makes within-group negative correlation
    ~200x denser than text8's — too harsh a G bar. This corpus keeps the
    REAL collision structure (71k vocab, zipf(1) unigram law, the frozen
    bench batch shape) while planting recoverable ground truth:

    * clusters are ``cluster_size`` words of CONSECUTIVE zipf rank in
      [rank_lo, rank_hi) — homogeneous within-cluster frequency, clusters
      spanning the head-to-mid spectrum (ultra-head words act as
      stop-words and stay unplanted; deep-tail words occur too rarely to
      learn in a bounded run);
    * a sentence is topical with prob ``topical_rate`` (topic uniform
      over clusters); topical sentences draw each word from the cluster
      with prob ``p_in``, else from the global zipf law — so cluster
      words strongly co-occur on top of a realistic background.

    Returns {word: cluster_id} for the planted words.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()

    # consecutive-rank clusters, evenly spaced over [rank_lo, rank_hi)
    span = rank_hi - rank_lo
    stride = max(span // n_clusters, cluster_size)
    cluster_words = np.stack([
        np.arange(rank_lo + k * stride, rank_lo + k * stride + cluster_size)
        for k in range(n_clusters)])              # [C, size] word ids
    labels = {f"w{w}": k for k, ws in enumerate(cluster_words) for w in ws}

    n_sent = n_tokens // sent_len
    topical = rng.random(n_sent) < topical_rate
    topic = rng.integers(0, n_clusters, n_sent)
    words = rng.choice(vocab, size=(n_sent, sent_len), p=probs)
    in_cluster = (rng.random((n_sent, sent_len)) < p_in) & topical[:, None]
    member = rng.integers(0, cluster_size, (n_sent, sent_len))
    planted = cluster_words[topic[:, None], member]
    words = np.where(in_cluster, planted, words)
    # guarantee full-vocab dictionary coverage (as bench.py's corpus does):
    # a shuffled enumeration padded to a whole number of sentences
    perm = rng.permutation(vocab)
    pad = (-len(perm)) % sent_len
    cover = np.concatenate([perm, perm[:pad]]).reshape(-1, sent_len)
    words[:cover.shape[0], :] = cover
    with open(path, "w") as f:
        for row in words:
            f.write(" ".join(f"w{w}" for w in row) + "\n")
    return labels


def probe_subset(words, vecs, labels, bands=None):
    """(nn_purity, cosine_gap[, per-band rows]) over ONLY the planted
    cluster words — at 71k vocab the full sim matrix is 20 GB; the
    planted subset (C x size words) is what ground truth exists for
    anyway.

    ``bands``: optional list of (name, lo_rank, hi_rank) — word ids ARE
    zipf ranks in the synthetic corpora, so banding by id splits the
    planted clusters into frequency strata. The per-band rows answer the
    TAIL-sensitivity question the aggregate can hide: an approximation
    (e.g. G-shared negatives) could hold the head and quietly damage
    rare words.
    """
    idx = [i for i, w in enumerate(words) if w in labels]
    lab = np.array([labels[words[i]] for i in idx])
    rank = np.array([int(words[i][1:]) for i in idx])   # "w123" -> 123
    sub = vecs[idx]
    unit = sub / np.maximum(np.linalg.norm(sub, axis=1, keepdims=True), 1e-9)
    sim = unit @ unit.T
    np.fill_diagonal(sim, -np.inf)
    nn = sim.argmax(axis=1)
    hit = lab == lab[nn]
    same = lab[:, None] == lab[None, :]
    off = ~np.eye(len(idx), dtype=bool)

    def _gap(mask_rows):
        s = sim[mask_rows]
        sm = same[mask_rows]
        offm = off[mask_rows]
        return float(s[sm & offm].mean()
                     - s[~sm & offm][:: max(len(idx) // 64, 1)].mean())

    purity = float(hit.mean())
    gap = _gap(np.ones(len(idx), bool))
    if bands is None:
        return purity, gap
    rows = []
    for name, lo, hi in bands:
        m = (rank >= lo) & (rank < hi)
        if m.sum() == 0:
            continue
        rows.append({"band": name, "n": int(m.sum()),
                     "purity": float(hit[m].mean()), "gap": _gap(m)})
    return purity, gap, rows


def load_vectors(path: str):
    words, vecs = [], []
    with open(path) as f:
        f.readline()
        for line in f:
            parts = line.rstrip("\n").split(" ")
            words.append(parts[0])
            vecs.append([float(x) for x in parts[1:]])
    return words, np.asarray(vecs, np.float32)


def probe(words, vecs, labels):
    """(nn_purity, cosine_gap) over content words."""
    norms = np.linalg.norm(vecs, axis=1, keepdims=True)
    unit = vecs / np.maximum(norms, 1e-9)
    lab = np.array([labels.get(w, -1) for w in words])
    content = lab >= 0
    sim = unit @ unit.T
    np.fill_diagonal(sim, -np.inf)
    sim[:, ~content] = -np.inf          # neighbors restricted to content
    nn = sim.argmax(axis=1)
    purity = float(np.mean(lab[content] == lab[nn[content]]))
    c = np.flatnonzero(content)
    s = unit[c] @ unit[c].T
    same = lab[c][:, None] == lab[c][None, :]
    off = ~np.eye(len(c), dtype=bool)
    gap = float(s[same & off].mean() - s[~same].mean())
    return purity, gap


def run_config(corpus, labels, tag, batch_size, row_mean, cap,
               epochs=3, size=64, static=False, shared=0):
    import multiverso_tpu as mv
    from multiverso_tpu.apps.wordembedding import Word2VecConfig, train
    from multiverso_tpu.runtime import Session

    Session._instance = None
    mv.init([tag])
    try:
        cfg = Word2VecConfig(embedding_size=size, window=5, negative=5,
                             batch_size=batch_size, init_lr=0.05,
                             row_mean_updates=row_mean, row_update_cap=cap,
                             row_mean_static=static, seed=3,
                             shared_negatives=shared)
        out = tempfile.NamedTemporaryFile(suffix=".vec", delete=False).name
        res = train(corpus, out, cfg, epochs=epochs, min_count=1,
                    sample=1e-3, log_every=0)
        words, vecs = load_vectors(out)
        os.unlink(out)
        purity, gap = probe(words, vecs, labels)
        return {"tag": tag, "batch": batch_size,
                "row_mean": row_mean, "cap": cap,
                "loss": res.final_loss, "pairs_per_sec": res.pairs_per_sec,
                "nn_purity": purity, "cos_gap": gap}
    finally:
        mv.shutdown()
        Session._instance = None


def run_realscale_config(corpus, labels, tag, shared, epochs=3,
                         heldout_corpus=None, heldout_counts=None):
    """One G configuration at the FROZEN bench shape (BASELINE.md):
    71k vocab, dim 200, 64k batch, oversample 2.5, negative pool,
    static capped row-mean — the exact config whose throughput the
    bench records, so the quality verdict transfers 1:1.

    With ``heldout_corpus`` set, also evaluates the trained model's
    held-out skip-gram NS likelihood (:func:`heldout_nll`) — the
    generalization guard the in-sample loss and the saturating
    planted-cluster bar cannot provide (VERDICT r4 item 4)."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps.wordembedding import Word2VecConfig, train
    from multiverso_tpu.runtime import Session

    Session._instance = None
    mv.init([tag, "-log_level=error"])
    try:
        cfg = Word2VecConfig(embedding_size=200, window=5, negative=5,
                             batch_size=65536, init_lr=0.025,
                             oversample=2.5, neg_pool_size=1 << 22,
                             row_mean_updates=True, row_mean_static=True,
                             shared_negatives=shared, seed=3)
        out = tempfile.NamedTemporaryFile(suffix=".vec", delete=False).name
        out_ctx = (tempfile.NamedTemporaryFile(
            suffix=".vec", delete=False).name if heldout_corpus else None)
        res = train(corpus, out, cfg, epochs=epochs, min_count=1,
                    sample=1e-3, log_every=0, output_path_ctx=out_ctx)
        words, vecs = load_vectors(out)
        row = {"tag": tag, "shared": shared, "loss": res.final_loss,
               "pairs_per_sec": res.pairs_per_sec}
        if heldout_corpus:
            row["heldout_nll"] = heldout_nll(
                words, vecs, load_vectors(out_ctx)[1], heldout_corpus,
                heldout_counts)
            os.unlink(out_ctx)
        os.unlink(out)
        purity, gap, bands = probe_subset(
            words, vecs, labels,
            bands=[("head [100,1k)", 100, 1000),
                   ("mid [1k,5k)", 1000, 5000),
                   ("tail [5k,20k)", 5000, 20000)])
        row.update({"nn_purity": purity, "cos_gap": gap, "bands": bands})
        return row
    finally:
        mv.shutdown()
        Session._instance = None


def split_heldout(corpus: str, train_path: str, heldout_path: str,
                  every: int = 8, skip_first: int = 0):
    """Interleaved sentence split: every ``every``-th line past the first
    ``skip_first`` (the full-vocab coverage block, which must stay in
    TRAIN so the dictionary reaches every word) goes to the held-out
    file, the rest to the train file. Interleaving keeps both splits on
    the same distribution (the corpus has no document structure)."""
    with open(corpus) as f, open(train_path, "w") as tr, \
            open(heldout_path, "w") as ho:
        for i, line in enumerate(f):
            if i >= skip_first and (i - skip_first) % every == 0:
                ho.write(line)
            else:
                tr.write(line)


def heldout_nll(words, w_in, w_ctx, heldout_corpus, counts,
                window: int = 5, negative: int = 5,
                max_pairs: int = 2_000_000, seed: int = 17) -> float:
    """Mean held-out skip-gram negative-sampling NLL.

    For each held-out (center c, context o) pair within the full
    window: ``-log sig(u_o . v_c) - sum_k log sig(-u_nk . v_c)`` with
    ``negative`` FRESH exact unigram^0.75 draws (fixed seed) — the
    reference training objective (``WE/src/wordembedding.cpp:120-168``)
    evaluated on unseen text, so it measures what any training-time
    negative-sharing relaxation (G) does to generalization, on the
    exact-draw objective regardless of how the model was trained.
    Deterministic: full window (no shrink), no subsampling, seeded
    negatives and pair subsample.
    """
    idx = {w: i for i, w in enumerate(words)}
    sents = []
    with open(heldout_corpus) as f:
        for line in f:
            toks = line.split()
            ids = [idx[t] for t in toks if t in idx]
            if len(ids) > 1:
                sents.append(np.asarray(ids, np.int32))
    # window pairs, vectorized per offset (sentences are fixed-length
    # lines here, but ragged input works too)
    lens = np.asarray([len(s) for s in sents])
    centers, contexts = [], []
    for d in range(1, window + 1):
        keep = lens > d
        c = np.concatenate([sents[i][:-d] for i in np.flatnonzero(keep)])
        o = np.concatenate([sents[i][d:] for i in np.flatnonzero(keep)])
        centers += [c, o]          # both directions
        contexts += [o, c]
    centers = np.concatenate(centers)
    contexts = np.concatenate(contexts)
    rng = np.random.default_rng(seed)
    if centers.size > max_pairs:
        sel = rng.choice(centers.size, size=max_pairs, replace=False)
        centers, contexts = centers[sel], contexts[sel]
    # counts is TOKEN-ID-indexed ("w{id}"), but embedding rows follow the
    # dictionary's first-occurrence order (the corpus opens with a
    # SHUFFLED coverage block, so rows are a random permutation of ids);
    # realign the negative law to ROW order so draws index real words
    tok_ids = np.asarray([int(w[1:]) for w in words])
    p = counts[tok_ids].astype(np.float64) ** 0.75
    p /= p.sum()
    w_in = np.asarray(w_in, np.float32)
    w_ctx = np.asarray(w_ctx, np.float32)
    total, n = 0.0, 0
    chunk = 1 << 18
    for i in range(0, centers.size, chunk):
        c = centers[i:i + chunk]
        o = contexts[i:i + chunk]
        v = w_in[c]                                   # [m, D]
        pos = np.einsum("md,md->m", w_ctx[o], v)
        negs = rng.choice(len(p), size=(c.size, negative), p=p)
        neg = np.einsum("mkd,md->mk", w_ctx[negs], v)
        # -log sig(x) = logaddexp(0, -x), stable
        total += np.logaddexp(0, -pos).sum()
        total += np.logaddexp(0, neg).sum()
        n += c.size
    return float(total / n)


_RS_BEGIN = "<!-- realscale:begin -->"
_RS_END = "<!-- realscale:end -->"


def realscale_sweep(out_path: str = "", quick: bool = False,
                    gs=(0, 16, 32, 64)):
    """VERDICT r3 item 7: re-probe the G cap at the real text8 shape."""
    gs = tuple(gs)
    if not gs or gs[0] != 0:
        # rows[0] is used as the exact-draw reference below; a --gs list
        # not starting with 0 would silently rebase every Δ column on a
        # shared-draw run (ADVICE r4)
        gs = (0,) + tuple(g for g in gs if g != 0)
    corpus = os.path.join(tempfile.gettempdir(), "eq_real_corpus.txt")
    n_tokens = 2_000_000 if quick else 8_000_000
    n_clusters = 250 if quick else 1000
    epochs = 2 if quick else 3
    labels = make_realscale_corpus(corpus, n_tokens=n_tokens,
                                   n_clusters=n_clusters)
    rows = []
    for g in gs:
        r = run_realscale_config(corpus, labels, f"rs_g{g}", g,
                                 epochs=epochs)
        print(f"realscale G={g}: loss {r['loss']:.4f} purity "
              f"{r['nn_purity']:.3f} gap {r['cos_gap']:.3f} "
              f"({r['pairs_per_sec'] / 1e6:.2f}M pairs/s)", flush=True)
        rows.append(r)
    ref = rows[0]

    def band_parity(r):
        """Tail-sensitivity bar: EVERY frequency band must hold parity
        (purity within 0.02, gap within 10% of the same band's exact-draw
        baseline) — the aggregate can hide rare-word damage."""
        ref_bands = {b["band"]: b for b in ref["bands"]}
        return all(b["purity"] >= ref_bands[b["band"]]["purity"] - 0.02
                   and b["gap"] >= 0.9 * ref_bands[b["band"]]["gap"]
                   for b in r["bands"] if b["band"] in ref_bands)

    ok = [r for r in rows[1:]
          if r["nn_purity"] >= ref["nn_purity"] - 0.02
          and r["cos_gap"] >= 0.9 * ref["cos_gap"]
          and band_parity(r)]
    best = max((r["shared"] for r in ok), default=0)
    # Loss guard (round 4): the planted-cluster bar is ONE-SIDED (it
    # rejects degradation; improvement passes) and saturates at real
    # scale — gaps improve monotonically with G — so it stops
    # discriminating. Final training loss on the actual objective is
    # the guard the bar cannot provide: cap the recommendation at <1%
    # drift off the exact-draw baseline.
    guarded = [r for r in ok if r["loss"] <= 1.01 * ref["loss"]]
    best_guarded = max((r["shared"] for r in guarded), default=0)
    lines = [
        _RS_BEGIN,
        "## Real-scale G probe (71k-vocab, frozen bench config)",
        "",
        "Produced by `tools/embedding_quality.py --realscale`: the full",
        f"text8 vocabulary (71,291 words, zipf unigram law), {n_clusters}",
        "planted 8-word clusters of consecutive rank in [100, 20k),",
        f"{n_tokens / 1e6:.0f}M tokens, {epochs} epochs, at the EXACT frozen",
        "bench config (dim 200, 64k batch, oversample 2.5, static capped",
        "row-mean — BASELINE.md). The r3 probe above is ~200x denser in",
        "within-group negative correlation than text8; this one has the",
        "real collision structure, so its G verdict transfers to the",
        "bench corpus 1:1. (pairs/s below is THIS probe run's own rate,",
        "not the idle-chip bench — see BASELINE.md for bench rates.)",
        "",
        "| G | final loss | Δloss | NN purity | cos gap | pairs/s |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        dl = ("—" if r is ref else
              f"{(r['loss'] / ref['loss'] - 1) * 100:+.1f}%")
        lines.append(f"| {r['shared']} | {r['loss']:.4f} | {dl} "
                     f"| {r['nn_purity']:.3f} | {r['cos_gap']:.3f} "
                     f"| {r['pairs_per_sec'] / 1e6:.2f}M |")
    lines += [
        "",
        "Per-frequency-band breakdown (word ids are zipf ranks; the",
        "aggregate could hide rare-word damage — G-shared draws touch",
        "head rows most, so the TAIL bands are the sensitivity check):",
        "",
        "| G | " + " | ".join(
            f"{b['band']} purity / gap" for b in rows[0]["bands"]) + " |",
        "|---|" + "---|" * len(rows[0]["bands"]),
    ]
    for r in rows:
        cells = " | ".join(f"{b['purity']:.3f} / {b['gap']:.3f}"
                           for b in r["bands"])
        lines.append(f"| {r['shared']} | {cells} |")
    lines += [
        "",
        (f"Parity bar (ONE-SIDED degradation bar: purity within 0.02 "
         f"below and cos-gap no more than 10% below the exact-draw G=0 "
         f"baseline — improvement passes — in aggregate AND in every "
         f"frequency band): largest G at parity = **{best}**. "
         f"Loss guard (final training loss within 1% of exact-draw — "
         f"the check the saturating cluster bar cannot make): largest "
         f"G = **{best_guarded}**. The bench default is the loss-guarded "
         f"value, additionally capped by measured on-chip throughput "
         f"saturation (BASELINE.md)."),
        _RS_END,
    ]
    text = "\n".join(lines)
    if out_path:
        from tools.docsplice import splice

        splice(out_path, text, _RS_BEGIN, _RS_END)
        print(f"wrote {out_path}")
    else:
        print(text)
    return rows, best


_HO_BEGIN = "<!-- heldout:begin -->"
_HO_END = "<!-- heldout:end -->"


def heldout_sweep(out_path: str = "", quick: bool = False,
                  gs=(0, 16, 64, 128)):
    """VERDICT r4 item 4: a HELD-OUT likelihood guard for the G default.

    The realscale sweep's loss guard is in-sample (final training loss);
    this sweep splits the realscale corpus, trains each G on the train
    split at the frozen bench config, and scores held-out skip-gram NS
    NLL under the EXACT-draw objective (:func:`heldout_nll`). The G cap
    criterion becomes out-of-sample: largest G whose held-out NLL stays
    within 1% of the exact-draw baseline's.
    """
    gs = tuple(gs)
    if not gs or gs[0] != 0:
        gs = (0,) + tuple(g for g in gs if g != 0)
    tmp = tempfile.gettempdir()
    corpus = os.path.join(tmp, "eq_ho_full.txt")
    train_c = os.path.join(tmp, "eq_ho_train.txt")
    held_c = os.path.join(tmp, "eq_ho_held.txt")
    n_tokens = 2_000_000 if quick else 8_000_000
    n_clusters = 250 if quick else 1000
    epochs = 2 if quick else 3
    sent_len = 16
    labels = make_realscale_corpus(corpus, n_tokens=n_tokens,
                                   n_clusters=n_clusters,
                                   sent_len=sent_len)
    # the full-vocab coverage block must stay in TRAIN (dictionary
    # coverage); hold out every 8th sentence after it
    vocab = 71291
    skip = -(-vocab // sent_len)
    split_heldout(corpus, train_c, held_c, every=8, skip_first=skip)
    # negative-draw law for the evaluation = TRAIN-corpus unigram counts
    # (what training's sampler used)
    counts = np.zeros(vocab, np.int64)
    with open(train_c) as f:
        for line in f:
            ids = [int(t[1:]) for t in line.split()]
            np.add.at(counts, ids, 1)
    rows = []
    for g in gs:
        r = run_realscale_config(train_c, labels, f"ho_g{g}", g,
                                 epochs=epochs, heldout_corpus=held_c,
                                 heldout_counts=counts)
        print(f"heldout G={g}: train-loss {r['loss']:.4f} "
              f"heldout-NLL {r['heldout_nll']:.4f} "
              f"purity {r['nn_purity']:.3f}", flush=True)
        rows.append(r)
    ref = rows[0]
    guarded = [r for r in rows
               if r["heldout_nll"] <= 1.01 * ref["heldout_nll"]]
    best = max((r["shared"] for r in guarded), default=0)
    lines = [
        _HO_BEGIN,
        "## Held-out likelihood guard for the G default",
        "",
        "Produced by `tools/embedding_quality.py --heldout`: the",
        "realscale corpus split 7:1 (interleaved sentences; the",
        "full-vocab coverage block stays in train), each G trained on",
        "the train split at the frozen bench config, then scored on the",
        "held-out split as mean skip-gram negative-sampling NLL under",
        "the EXACT-draw objective (5 fresh unigram^0.75 negatives per",
        "pair, fixed seed, full window, no subsampling) — out-of-sample",
        "generalization on the reference objective, independent of the",
        "training-time draw-sharing relaxation being probed.",
        "",
        "| G | train loss | held-out NLL | ΔNLL vs exact |",
        "|---|---|---|---|",
    ]
    for r in rows:
        d = ("—" if r is ref else
             f"{(r['heldout_nll'] / ref['heldout_nll'] - 1) * 100:+.2f}%")
        lines.append(f"| {r['shared']} | {r['loss']:.4f} "
                     f"| {r['heldout_nll']:.4f} | {d} |")
    lines += [
        "",
        f"Held-out guard (NLL within 1% of the exact-draw baseline): "
        f"largest G = **{best}**. This — not the in-sample training "
        f"loss — is the cap criterion the bench default cites "
        f"(BASELINE.md); the in-sample loss guard and the saturating "
        f"planted-cluster bar remain as secondary checks "
        f"(sections above).",
        _HO_END,
    ]
    text = "\n".join(lines)
    if out_path:
        from tools.docsplice import splice

        splice(out_path, text, _HO_BEGIN, _HO_END)
        print(f"wrote {out_path}")
    else:
        print(text)
    return rows, best


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller corpus / fewer epochs")
    ap.add_argument("--realscale", action="store_true",
                    help="71k-vocab G probe at the frozen bench config "
                         "(appends its own section to --out)")
    ap.add_argument("--gs", default="0,16,32,64",
                    help="comma-separated G values for --realscale")
    ap.add_argument("--heldout", action="store_true",
                    help="held-out NS-NLL G guard at the frozen bench "
                         "config (appends its own section to --out)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend; quality verdicts are "
                         "backend-independent")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    if args.cpu:
        import jax

        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    if args.heldout:
        gs = tuple(int(g) for g in args.gs.split(","))
        if args.gs == ap.get_default("gs"):
            gs = (0, 16, 64, 128)   # the VERDICT r4 item-4 sweep
        heldout_sweep(args.out, quick=args.quick, gs=gs)
        return 0
    if args.realscale:
        realscale_sweep(args.out, quick=args.quick,
                        gs=tuple(int(g) for g in args.gs.split(",")))
        return 0

    corpus = os.path.join(tempfile.gettempdir(), "eq_corpus.txt")
    n_sent = 8000 if args.quick else 30000
    epochs = 2 if args.quick else 3
    labels = make_clustered_corpus(corpus, n_sentences=n_sent)

    # vocab = 8*40 + 12 = 332 content+stop words. cap*vocab ~ 2.6k: the
    # 16k batch is ~50 expected hits per row -> deep in divergence regime.
    configs = [
        ("reference-semantics small batch", 1024, False, 8.0, False, 0),
        ("summed large batch", 16384, False, 8.0, False, 0),
        ("row-mean cap=1 large batch", 16384, True, 1.0, False, 0),
        ("row-mean cap=8 large batch", 16384, True, 8.0, False, 0),
        ("row-mean cap=32 large batch", 16384, True, 32.0, False, 0),
        ("row-mean cap=64 large batch", 16384, True, 64.0, False, 0),
        ("STATIC row-mean cap=8 large batch", 16384, True, 8.0, True, 0),
        # group-shared negatives (VERDICT r2 item 1): each group of G
        # consecutive pairs shares one K-negative draw — the 2.8x
        # throughput mode. Swept at the cap=8 large-batch baseline.
        ("shared negatives G=2, cap=8", 16384, True, 8.0, False, 2),
        ("shared negatives G=4, cap=8", 16384, True, 8.0, False, 4),
        ("shared negatives G=8, cap=8", 16384, True, 8.0, False, 8),
        ("shared negatives G=16, cap=8", 16384, True, 8.0, False, 16),
    ]
    rows = []
    for name, batch, rm, cap, static, shared in configs:
        r = run_config(corpus, labels, name, batch, rm, cap, epochs=epochs,
                       static=static, shared=shared)
        r["name"] = name
        r["shared"] = shared
        print(f"{name:36s} loss {r['loss']:.4f} "
              f"nn_purity {r['nn_purity']:.3f} gap {r['cos_gap']:.3f}",
              flush=True)
        rows.append(r)

    lines = [
        "# Embedding quality: batched semantics vs reference sequential",
        "",
        "Produced by `tools/embedding_quality.py` (synthetic 8-cluster corpus,",
        f"{n_sent} sentences, {epochs} epochs, dim 64, window 5, 5 negatives;",
        "higher nn-purity / cosine-gap = better cluster recovery; chance",
        "purity = 1/8 = 0.125).",
        "",
        "| config | batch | row_mean | cap | G | final loss | NN purity | cos gap |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['name']} | {r['batch']} | {r['row_mean']} | {r['cap']:g} "
            f"| {r.get('shared', 0)} "
            f"| {r['loss']:.4f} | {r['nn_purity']:.3f} | {r['cos_gap']:.3f} |")
    ref = rows[0]
    cap8 = next((r for r in rows if r["row_mean"] and r["cap"] == 8.0
                 and not r.get("shared")), None)
    lines += [
        "",
        f"Reference-semantics baseline purity: **{ref['nn_purity']:.3f}**.",
    ]
    if cap8 is not None:
        lines += [
            f"The default cap=8 at 16k batch reaches purity "
            f"{cap8['nn_purity']:.3f} / gap {cap8['cos_gap']:.3f} — parity "
            f"with the reference-semantics baseline, while the uncapped sum "
            f"diverges (NaN) and very large caps re-diverge; this is the "
            f"evidence behind the `row_update_cap = 8` default.",
        ]
    shared_rows = [r for r in rows if r.get("shared")]
    if shared_rows and cap8 is not None:
        ok = [r for r in shared_rows
              if r["nn_purity"] >= ref["nn_purity"] - 0.02
              and r["cos_gap"] >= 0.9 * ref["cos_gap"]]
        best = max((r["shared"] for r in ok), default=0)
        lines += [
            "",
            "Group-shared negatives (`-shared_negatives=G`) share one",
            "K-negative draw across each group of G consecutive pairs,",
            "cutting the dominant negative gather/scatter traffic by G",
            "(same objective in expectation — every pair still sees K",
            "negatives from the unigram^0.75 law, they are just correlated",
            "within a group).",
            (f"Parity bar: purity within 0.02 and cos-gap within 10% of the "
             f"reference-semantics baseline (one-sided — improvement "
             f"passes). Largest G at parity: **{best}** — on THIS harsh "
             f"probe; the real-scale probe below supersedes it for the "
             f"bench default (loss-guarded, see its section)."
             if best else
             "No swept G met the parity bar (purity within 0.02, cos-gap "
             "within 10% of baseline)."),
            "",
            "Note the probe is deliberately harsh on G: its ~332-word",
            "vocab makes within-group negative correlation ~200x denser",
            "than text8's 71k vocab (each word re-drawn ~G*K*B/(G*vocab)",
            "times per step), so a G that passes here has headroom at",
            "real vocab sizes — which is why the real-scale probe, not",
            "this one, sets the bench default.",
        ]
    lines += [
        "",
        "The capped row-mean path is the large-batch divergence guard: the",
        "auto default in `apps/wordembedding.py` estimates the hottest",
        "row's expected colliding grads per step from the sampling laws",
        "and enables the cap past ~512 expected hits (stable at ~150,",
        "divergent by ~2300 — zipf corpora concentrate collisions on the",
        "head words). See",
        "`models/word2vec.py` `row_mean_updates`/`row_update_cap` docs for",
        "the mechanism; reference sequential loop:",
        "`Applications/WordEmbedding/src/wordembedding.cpp:120-168`.",
        "",
    ]
    text = "\n".join(lines)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out}")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
