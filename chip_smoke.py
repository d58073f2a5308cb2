"""Chip smoke: the system's main paths, once each, on one TPU v5e.

One process, one ``mv.init``, four phases in order through the entry
points a user calls, at the repo's own full widths (depth and weights
are the repo's too: weights are random, from a seed):

* tables    — a 71,291 x 200 bf16 matrix table and a 1,000,000-element
              AdaGrad array table: add / add_rows / get against a numpy
              fold of the same deltas;
* word2vec  — the frozen ``bench.py`` configuration: three
              ``train_device_steps(25)`` dispatches, then one
              ``train_batch`` on a fixed batch against a float32
              ``jax.numpy`` step written here;
* lm        — three ``TransformerLM.train_batch`` steps at d_model 768 /
              12 layers / seq 2048 / batch 4 / bf16 with
              ``attention="flash"``; the lowered step must hold the
              Pallas kernel (``tpu_custom_call``);
* serving   — ``InferenceServer.register_decoder`` on that LM with the
              engine's defaults, eight requests (a shared prefix and a
              full repeat among them), every output token-identical to
              ``greedy_decode`` on the same chip — in bf16 if bf16 gives
              identity, else in float32, else in float32 with
              full-precision products; each rung that does not is
              printed as a finding (``LADDER``).

``--chips 4`` runs instead, and only, what exists across chips: the
server-sharded table (``-mesh_shape=2,2`` and ``1,4``),
``dp_sync="dispatch"`` word2vec over two workers against its one-worker
oracle, and a ``decode_tp=2`` engine against the ``decode_tp=1`` engine
(same ladder).

Every phase prints one JSON line of facts (set-up and steady seconds
apart, peak device bytes). The LAST line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Off-TPU, or
when any phase fails, the last line says ``"ok": false`` with the phase
and the exit code is 1. Nothing here falls back to another backend.

Usage: python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import json
import os
import subprocess
import sys
import tempfile
import time
import traceback

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 21
# the paper's text8 table shape (BASELINE.json config 2)
VOCAB, DIM = 71291, 200
ARRAY_SIZE = 1_000_000
W2V_CORPUS_WORDS = 4_000_000         # bench.make_corpus default
W2V_BATCH, W2V_GROUP = 65536, 64     # bench.py frozen config
W2V_DISPATCHES, W2V_STEPS = 3, 25
# tools/lm_mfu.py flagship shape
LM = dict(vocab_size=256, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
          max_seq=2048)
LM_BATCH, LM_SEQ, LM_STEPS = 4, 2048, 3
SERVE = dict(slots=32, max_prompt=1024, max_new=64)
SERVE_REQUESTS, SERVE_MIN_PROMPT, SERVE_SHARED_PREFIX = 8, 16, 256
# --chips 4: dp word2vec (hierarchical softmax keeps the step RNG-free,
# so the one-worker oracle is reproducible — tests/test_word2vec.py)
DP_BATCH, DP_STEPS, DP_DISPATCHES, DP_ATOL = 8192, 3, 2, 2e-5


class SmokeFailure(Exception):
    """A phase's check did not hold."""


class PhaseFailed(Exception):
    """Whatever ended a phase (``__cause__``), under the phase's name."""

    def __init__(self, phase: str) -> None:
        super().__init__(phase)
        self.phase = phase


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def run_phase(name: str, fn, *args):
    """No phase is caught and passed over: its failure ends the run."""
    try:
        return fn(*args)
    except Exception as exc:
        raise PhaseFailed(name) from exc


def _report(phase: str, **facts) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _bf16_close(got, ref, size, ulps: float) -> bool:
    """``got`` within ``ulps`` bf16 steps of ``ref``. A bf16 add rounds
    at the size of its operands, not of its result, so the caller says
    at which ``size`` a step (2**-8 of it) is taken."""
    err = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32))
    return bool(np.all(err <= ulps * 2.0 ** -8 * size))


# -- host pipeline ------------------------------------------------------------
def build_native() -> str:
    """Build the native reader from tracked sources, so that a found
    ``cpp/libmultiverso_tpu.so`` of unknown age never decides which host
    pipeline runs. Returns the path the word2vec phase will take."""
    from multiverso_tpu import native

    made = subprocess.run(
        ["make", "-B", "-C", os.path.join(_REPO, "cpp"),
         "libmultiverso_tpu.so"], capture_output=True, text=True)
    check(made.returncode == 0,
          f"make -C cpp failed:\n{made.stdout[-800:]}\n{made.stderr[-800:]}")
    check(native.load() is not None, "built native library did not load")
    return "native (built by make -C cpp)"


# -- phase: tables -------------------------------------------------------------
def phase_tables(mv) -> None:
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from multiverso_tpu.updaters import AddOption

    bf16 = ml_dtypes.bfloat16
    rng = np.random.default_rng(SEED)
    chip = jax.devices()[0]

    def rounds():
        """(dense delta, row ids, row values, array delta) per round."""
        ids = rng.choice(VOCAB, 4096, replace=False).astype(np.int32)
        return (rng.standard_normal((VOCAB, DIM)).astype(np.float32),
                ids, rng.standard_normal((4096, DIM)).astype(np.float32),
                (rng.standard_normal(ARRAY_SIZE) * 0.01).astype(np.float32))

    t0 = time.perf_counter()
    matrix = mv.create_table("matrix", VOCAB, DIM, dtype=jnp.bfloat16)
    array = mv.create_table("array", ARRAY_SIZE, updater="adagrad")
    create_s = time.perf_counter() - t0
    for table in (matrix, array):
        check(table.array.devices() == {chip},
              f"{table.name} lives on {table.array.devices()}, not {chip}")

    # numpy fold: the matrix in bf16 steps (default updater, data +=
    # delta), the array by the AdaGrad formula at AddOption's defaults
    ref_m = np.zeros((VOCAB, DIM), bf16)
    ref_a = np.zeros(ARRAY_SIZE, np.float32)
    g_sqr = np.zeros(ARRAY_SIZE, np.float32)
    opt = AddOption()
    times = []                  # the tables' calls only, not the fold
    for _ in range(2):          # round 1 compiles, round 2 is steady
        dense, ids, rows, grad = rounds()
        t_round = time.perf_counter()
        matrix.add(dense)
        matrix.add_rows(ids, rows)
        array.add(grad)
        got_m, got_a = matrix.get(), array.get()
        times.append(time.perf_counter() - t_round)
        ref_m = (ref_m.astype(np.float32)
                 + dense.astype(bf16).astype(np.float32)).astype(bf16)
        ref_m[ids] = (ref_m[ids].astype(np.float32)
                      + rows.astype(bf16).astype(np.float32)).astype(bf16)
        g_sqr = g_sqr + grad * grad
        ref_a = ref_a - (opt.rho / np.sqrt(g_sqr + 1e-6)) * grad \
            / opt.learning_rate                 # 1e-6: updaters' epsilon
        check(got_m.shape == (VOCAB, DIM) and got_a.shape == (ARRAY_SIZE,),
              f"get() shapes {got_m.shape} {got_a.shape}")
        # the fold is itself in bf16 steps: one step of slack
        check(_bf16_close(got_m, ref_m, np.abs(ref_m.astype(np.float32))
                          + np.abs(dense), ulps=1.0),
              "matrix add/add_rows/get disagrees with the numpy fold")
        check(bool(np.allclose(got_a, ref_a, rtol=1e-4, atol=1e-5)),
              "adagrad array add/get disagrees with the numpy fold")
    check(matrix.version == 4 and array.version == 2,
          f"table versions {matrix.version}, {array.version}")
    _report("tables", setup_s=round(create_s + times[0], 3),
            steady_s=round(times[1], 3),
            matrix=[VOCAB, DIM, "bfloat16"],
            array=[ARRAY_SIZE, "float32", "adagrad"])


# -- phase: word2vec -----------------------------------------------------------
def w2v_reference_step(w_in, w_out, centers, contexts, negs, lr,
                       scale_in, scale_out):
    """One skip-gram negative-sampling step in float32 ``jax.numpy``:
    every pair's gradient taken at the pre-step tables and summed per
    row, each K-negative draw shared by ``G`` consecutive pairs, row
    updates scaled by the expected-count cap (``row_mean_static``).

    Returns the new tables, the loss, and per table the error a bf16
    table may show against them (:func:`_bf16_bound`)."""
    import jax
    import jax.numpy as jnp

    # the chip's default matmul precision rounds float32 operands to
    # bf16; a float32 reference asks for the full product
    einsum = functools.partial(jnp.einsum,
                               precision=jax.lax.Precision.HIGHEST)
    B, D = centers.shape[0], w_in.shape[1]
    n_groups = negs.shape[0]
    h = w_in[centers]
    u_pos = w_out[contexts]
    s_pos = jnp.clip(jnp.sum(h * u_pos, -1), -30.0, 30.0)
    g_pos = (jax.nn.sigmoid(s_pos) - 1.0)[:, None]
    u_neg = w_out[negs]                                    # [B/G, K, D]
    hg = h.reshape(n_groups, B // n_groups, D)
    s_neg = jnp.clip(einsum("gbd,gkd->gbk", hg, u_neg), -30.0, 30.0)
    g_neg = jax.nn.sigmoid(s_neg)
    loss = (jnp.sum(jax.nn.softplus(s_pos) - s_pos)
            + jnp.sum(jax.nn.softplus(s_neg))) / B
    flat = negs.reshape(-1)
    rate_in = lr * scale_in[centers][:, None]
    rate_pos = lr * scale_out[contexts][:, None]
    rate_neg = lr * scale_out[flat][:, None]

    def grads(g_pos, u_pos, g_neg, u_neg, hg):
        """(d loss / d h, d / d positive rows, d / d negative rows)."""
        return (g_pos * u_pos + einsum("gbk,gkd->gbd", g_neg,
                                       u_neg).reshape(B, D),
                g_pos * hg.reshape(B, D),
                einsum("gbk,gbd->gkd", g_neg, hg).reshape(-1, D))

    d_in, d_pos, d_neg = grads(g_pos, u_pos, g_neg, u_neg, hg)
    new_in = w_in.at[centers].add(-rate_in * d_in)
    new_out = w_out.at[contexts].add(-rate_pos * d_pos).at[flat].add(
        -rate_neg * d_neg)
    # the same sums over absolute terms: how large each update's parts are
    a_in, a_pos, a_neg = grads(*map(jnp.abs, (g_pos, u_pos, g_neg, u_neg,
                                               hg)))
    return (new_in, new_out, loss,
            _bf16_bound(w_in, [(centers, rate_in * a_in)]),
            _bf16_bound(w_out, [(contexts, rate_pos * a_pos),
                                (flat, rate_neg * a_neg)]))


def _bf16_bound(before, updates):
    """Per entry, how far a bf16 table that takes ``updates`` (lists of
    rows and absolute-term update sizes) one by one may end from the
    float32 sum. Each of the ``n`` adds a row takes rounds a running
    value no larger than ``|before|`` plus all the terms, by at most
    bf16's unit roundoff (2**-8 of it); so does each update's own cast
    to bf16, and the chip's default matmul (bf16 operands) inside the
    trainer's step."""
    import jax.numpy as jnp

    size = jnp.abs(before)
    n = jnp.zeros(before.shape[0], jnp.float32)
    for rows, terms in updates:
        size = size.at[rows].add(terms)
        n = n.at[rows].add(1.0)
    return (n[:, None] + 2.0) * 2.0 ** -8 * size


def _static_scales(counts, discard, batch, negative, cap):
    """Expected-count row scales, as ``Word2VecConfig.row_mean_static``
    documents them: ``min(E, cap) / max(E, 1)``."""
    eff = counts * np.clip(1.0 - discard, 0.0, 1.0)
    p_eff = eff / eff.sum()
    p_neg = counts ** 0.75 / np.sum(counts ** 0.75)

    def scale(e):
        c = np.maximum(e, 1.0)
        return (np.minimum(c, cap) / c).astype(np.float32)

    return (scale(batch * p_eff),
            scale(batch * p_eff + batch * negative * p_neg))


def phase_word2vec(mv, native_path: str) -> None:
    import jax
    import jax.numpy as jnp

    import bench
    from multiverso_tpu.apps.wordembedding import (Dictionary, encode_corpus,
                                                   subsample_probs)
    from multiverso_tpu.models.word2vec import (Word2Vec, Word2VecConfig,
                                                build_unigram_alias,
                                                pack_alias_table,
                                                sample_negatives)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="mv_smoke_") as tmp:
        corpus = os.path.join(tmp, "corpus.txt")
        bench.make_corpus(corpus, n_words=W2V_CORPUS_WORDS, vocab=VOCAB)
        dictionary = Dictionary.build(corpus, min_count=1)
        ids, sent_ids = encode_corpus(corpus, dictionary)
    check(dictionary.vocab_size == VOCAB,
          f"dictionary holds {dictionary.vocab_size} words, not {VOCAB}")
    counts = np.asarray(dictionary.counts, np.float64)
    discard = subsample_probs(counts, 1e-3).astype(np.float32)
    host_s = time.perf_counter() - t0

    def config():
        # bench.py's frozen configuration, value for value
        return Word2VecConfig(vocab_size=VOCAB, embedding_size=DIM,
                              window=5, negative=5, init_lr=0.025,
                              batch_size=W2V_BATCH,
                              oversample=2.5, neg_pool_size=1 << 22,
                              row_mean_updates=True, row_mean_static=True,
                              shared_negatives=W2V_GROUP)

    t0 = time.perf_counter()
    w_in = mv.create_table("matrix", VOCAB, DIM, init_value="random",
                           dtype=jnp.bfloat16)
    w_out = mv.create_table("matrix", VOCAB, DIM, dtype=jnp.bfloat16)
    model = Word2Vec(config(), w_in, w_out, counts=counts)
    model.total_words = 10 ** 9
    model.load_corpus_chunk(ids, sent_ids, discard)
    version0 = w_in.version
    losses, pairs, times = [], [], []
    for _ in range(W2V_DISPATCHES):
        t_call = time.perf_counter()
        loss, count = model.train_device_steps(W2V_STEPS)
        losses.append(float(loss))
        pairs.append(float(count))
        times.append(time.perf_counter() - t_call)
    setup = time.perf_counter() - t0 - sum(times[1:])
    check(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    check(min(pairs) > 0, f"no pairs trained: {pairs}")
    check(w_in.version == version0 + W2V_DISPATCHES
          and w_out.version == W2V_DISPATCHES,
          f"table versions did not move: {w_in.version}, {w_out.version}")

    # one train_batch on a fixed batch against the float32 reference. A
    # second trainer on the same tables starts from the seed's key, so
    # the reference can draw the same negatives.
    fixed = Word2Vec(config(), w_in, w_out, counts=counts)
    fixed.total_words = model.total_words
    fixed.load_corpus_chunk(ids, sent_ids, discard)   # static row scales
    rng = np.random.default_rng(SEED + 1)
    centers = rng.integers(0, VOCAB, W2V_BATCH).astype(np.int32)
    contexts = rng.integers(0, VOCAB, W2V_BATCH).astype(np.int32)
    before_in, before_out = w_in.get(), w_out.get()
    lr = fixed.current_lr()
    t_call = time.perf_counter()
    loss = float(fixed.train_batch(centers, contexts))
    got_in, got_out = w_in.get(), w_out.get()
    batch_s = time.perf_counter() - t_call

    cfg = fixed.config
    sub = jax.random.split(jax.random.PRNGKey(cfg.seed))[1]
    thresh, alias = build_unigram_alias(counts)
    negs = sample_negatives(
        sub, pack_alias_table(jnp.asarray(thresh), jnp.asarray(alias)),
        (W2V_BATCH // W2V_GROUP, cfg.negative))
    scale_in, scale_out = _static_scales(
        counts, discard.astype(np.float64), W2V_BATCH, cfg.negative,
        cfg.row_update_cap)
    ref_in, ref_out, ref_loss, tol_in, tol_out = jax.jit(
        w2v_reference_step)(
        jnp.asarray(before_in, jnp.float32),
        jnp.asarray(before_out, jnp.float32), centers, contexts, negs,
        jnp.float32(lr), scale_in, scale_out)
    check(abs(loss - float(ref_loss)) <= 1e-3 * abs(float(ref_loss)),
          f"train_batch loss {loss} vs float32 reference {float(ref_loss)}")
    worst = {}
    for name, got, ref, before, tol in (
            ("w_in", got_in, ref_in, before_in, tol_in),
            ("w_out", got_out, ref_out, before_out, tol_out)):
        ref = np.asarray(ref)
        check(float(np.abs(ref - before.astype(np.float32)).max()) > 0,
              f"the reference step left {name} unchanged")
        err = np.abs(got.astype(np.float32) - ref)
        worst[name] = float(np.max(err / np.maximum(np.asarray(tol), 1e-30)))
        check(worst[name] <= 1.0,
              f"train_batch {name} is {worst[name]:.2f}x the bf16 rounding "
              "bound from the float32 reference")
    _report("word2vec", setup_s=round(setup, 3),
            steady_s=round(sum(times[1:]), 3), host_pipeline_s=round(host_s, 3),
            host_pipeline=native_path, losses=losses, pairs=pairs,
            train_batch_s=round(batch_s, 3), train_batch_loss=loss,
            reference_loss=float(ref_loss), share_of_bf16_bound=worst)


# -- phase: LM training step ---------------------------------------------------
def make_lm(dtype):
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)

    return TransformerLM(TransformerConfig(dtype=dtype, attention="flash",
                                           seed=SEED, **LM))


def lowered_step_text(lm, tokens) -> str:
    import jax.numpy as jnp

    return lm._step.lower(lm.params, lm._momentum,
                          jnp.asarray(tokens, jnp.int32)).as_text()


def phase_lm():
    import jax.numpy as jnp

    t0 = time.perf_counter()
    lm = make_lm(jnp.bfloat16)
    rng = np.random.default_rng(SEED + 2)
    tokens = rng.integers(0, LM["vocab_size"],
                          (LM_BATCH, LM_SEQ)).astype(np.int32)
    # best_attention gives way to the XLA reference without a word when
    # it judges the kernel not viable: the step must really hold it
    check("tpu_custom_call" in lowered_step_text(lm, tokens),
          "the lowered LM step holds no tpu_custom_call: attention='flash' "
          "did not dispatch to the Pallas kernel")
    losses, times = [], []
    for _ in range(LM_STEPS):
        t_call = time.perf_counter()
        losses.append(float(lm.train_batch(tokens)))
        times.append(time.perf_counter() - t_call)
    setup = time.perf_counter() - t0 - sum(times[1:])
    check(bool(np.all(np.isfinite(losses))), f"LM loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"LM loss did not fall over {LM_STEPS} steps: {losses}")
    check(lm.version == LM_STEPS, f"LM version {lm.version}")
    _report("lm", setup_s=round(setup, 3), steady_s=round(sum(times[1:]), 3),
            losses=losses, shape=dict(LM, batch=LM_BATCH, seq=LM_SEQ,
                                      dtype="bfloat16", attention="flash"))
    return lm


# -- phase: serving -------------------------------------------------------------
def make_prompts(n: int):
    """``n`` prompts with lengths spread over [SERVE_MIN_PROMPT,
    max_prompt], the shortest and the longest always there. The first
    two are served first; of the rest, prompt 2 shares prompt 0's first
    SERVE_SHARED_PREFIX tokens (a prefix-cache hit) and prompt 3 repeats
    prompt 1, one whole block, to the token (a full hit, whose last
    block is copied on write before decode rewrites it)."""
    rng = np.random.default_rng(SEED + 3)
    hi = SERVE["max_prompt"]
    lengths = [SERVE_SHARED_PREFIX * 5 // 4, SERVE_MIN_PROMPT,
               SERVE_SHARED_PREFIX * 7 // 4, SERVE_MIN_PROMPT, hi]
    lengths += [int(x) for x in rng.integers(SERVE_MIN_PROMPT, hi + 1,
                                             max(n - 5, 0))]
    prompts = [rng.integers(0, LM["vocab_size"], ln).astype(np.int32)
               for ln in lengths[:n]]
    prompts[2][:SERVE_SHARED_PREFIX] = prompts[0][:SERVE_SHARED_PREFIX]
    prompts[3][:] = prompts[1]
    return prompts


def serve(lm, prompts, name: str, **engine_kwargs):
    """Serve ``prompts`` through a fresh InferenceServer; returns
    (outputs, stats, pool_drift, set-up seconds, steady seconds)."""
    from multiverso_tpu.runtime import Session
    from multiverso_tpu.serving import InferenceServer

    t0 = time.perf_counter()
    srv = InferenceServer(f"smoke-{name}")
    eng = srv.register_decoder(name, lm, **SERVE, **engine_kwargs)
    eng.warmup()
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()

    def submit(p):
        return srv.submit(name, {"prompt": p, "max_new": SERVE["max_new"]})

    # the first two land their blocks in the prefix cache; the rest
    # arrive together, the two that reuse those blocks among them
    outs = [submit(p).result(timeout=600)["result"] for p in prompts[:2]]
    futures = [submit(p) for p in prompts[2:]]
    outs += [f.result(timeout=600)["result"] for f in futures]
    steady = time.perf_counter() - t0
    stats, drift = eng.stats(), eng.pool_drift()
    srv.stop()
    # a stopped server stays in the session's registry, and its engine's
    # pools on the chip, until shutdown: the ladder may build six engines
    # at this width, so let each go once it has answered
    Session.get().servers.remove(srv)
    return [np.asarray(o) for o in outs], stats, drift, setup, steady


def greedy_oracle(lm, prompts):
    """``greedy_decode`` on the snapshot params, same device and dtype,
    all prompts right-padded into one batch."""
    import jax

    from multiverso_tpu.models.transformer import greedy_decode

    params, _ = lm.snapshot_params()
    tokens = np.zeros((len(prompts), SERVE["max_prompt"]), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    out = jax.jit(lambda p, t, n: greedy_decode(
        lm.config, p, t, n, SERVE["max_new"]))(params, tokens, lengths)
    return np.asarray(out)


def mismatches(outs, want):
    """(count of differing sequences, first differing (request,
    position) or None); ``want`` rows may run longer than ``outs``."""
    first, count = None, 0
    for i, (got, ref) in enumerate(zip(outs, want)):
        diff = np.nonzero(got != np.asarray(ref)[:len(got)])[0]
        if diff.size:
            count += 1
            first = first or (i, int(diff[0]))
    return count, first


# Token identity is asked of the cheapest numerics that can give it. A
# rung that breaks it is a FINDING, printed when it is found; the phase
# stands on the first rung that holds and fails if none does. bf16
# rounds every activation to 8 bits, and the chip's default float32
# matmul still rounds its operands to bf16, so two programs that sum in
# a different order (chunked vs whole prefill, one chip vs two) can
# part at a near-tie; full-precision float32 products leave only the
# order of the float32 sums, which a bug would not hide behind.
LADDER = (("bfloat16", None), ("float32", None), ("float32", "highest"))


@contextlib.contextmanager
def matmul_precision(precision):
    """Process-wide (the engine's loop thread compiles too), restored."""
    import jax

    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", precision)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def first_identical(phase: str, compare, what: str):
    """Walk LADDER: ``compare(dtype name, rung name)`` serves and
    returns ``(mismatch count, first difference, result)``. Returns the
    first rung's (facts, result) whose outputs are token-identical."""
    findings = []
    for dtype, precision in LADDER:
        rung = {"dtype": dtype, "matmul_precision": precision or "default"}
        with matmul_precision(precision):
            n_bad, first, result = compare(
                dtype, f"{dtype}-{rung['matmul_precision']}")
        if n_bad == 0:
            return {**rung, "findings": findings}, result
        findings.append({**rung, "mismatched_requests": n_bad,
                         "first_difference": first})
        print(json.dumps({"phase": phase, "finding": f"{what} differ",
                          **findings[-1]}), flush=True)
    raise SmokeFailure(f"{what} differ on every rung: {findings}")


def check_engine_stats(stats, drift) -> None:
    check(stats["prefix_hits"] > 0, "no prefix-cache hit")
    check(stats["cow_copies"] > 0, "the full-prompt repeat copied no block")
    check(stats["step_traces"] == 1 and stats["prefill_traces"] == 1,
          f"traces: step {stats['step_traces']}, "
          f"prefill {stats['prefill_traces']}")
    check(stats["decode_step_retraces"] == 0,
          f"decode_step_retraces {stats['decode_step_retraces']}")
    check(drift is None, f"pool drift: {drift}")
    check(stats["watchdog_trips"] == 0,
          f"watchdog_trips {stats['watchdog_trips']}")


def phase_serving(lm) -> None:
    import jax.numpy as jnp

    prompts = make_prompts(SERVE_REQUESTS)

    def compare(dtype, rung):
        # bf16 serves the LM the last phase trained; float32 a fresh one
        model = lm if dtype == "bfloat16" else make_lm(jnp.float32)
        served = serve(model, prompts, f"lm-{rung}")
        t0 = time.perf_counter()
        n_bad, first = mismatches(served[0], greedy_oracle(model, prompts))
        return n_bad, first, served + (time.perf_counter() - t0,)

    facts, (outs, stats, drift, setup, steady, oracle_s) = first_identical(
        "serving", compare, "engine outputs and greedy_decode")
    check(all(len(o) == SERVE["max_new"] for o in outs),
          f"output lengths {[len(o) for o in outs]}")
    check_engine_stats(stats, drift)
    check(stats["completed"] == len(prompts),
          f"completed {stats['completed']} of {len(prompts)}")
    _report("serving", setup_s=round(setup, 3), steady_s=round(steady, 3),
            oracle_s=round(oracle_s, 3), requests=len(prompts),
            prompt_lengths=[len(p) for p in prompts],
            prefix_hits=stats["prefix_hits"],
            prefill_tokens_saved=stats["prefill_tokens_saved"],
            cow_copies=stats["cow_copies"], tokens=stats["tokens"],
            kv_pool_blocks=stats["kv_pool_blocks"],
            kv_bytes_per_device=stats["kv_bytes_per_device"], **facts)


# -- --chips 4 -------------------------------------------------------------------
def _bytes_per_device():
    import jax

    return {str(d): (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()}


def phase_sharded_table(mv, label: str) -> None:
    """The text8-shaped table on the session's mesh: every server shard
    on its own device(s), nothing parked on device 0."""
    import jax
    import jax.numpy as jnp

    before = _bytes_per_device()
    table = mv.create_table("matrix", VOCAB, DIM, dtype=jnp.bfloat16,
                            init_value="random", seed=SEED)
    table.flush()
    servers = mv.num_servers()
    devices = table.sharding.device_set
    check(len(devices) == len(jax.devices()) == 4,
          f"table sharding spans {len(devices)} of {len(jax.devices())} "
          "devices")
    shard_rows = table.padded_shape[0] // servers
    shapes = {s.data.shape for s in table.array.addressable_shards}
    check(shapes == {(shard_rows, DIM)},
          f"shard shapes {shapes}, expected {(shard_rows, DIM)}")
    starts = sorted({s.index[0].start or 0
                     for s in table.array.addressable_shards})
    check(starts == [i * shard_rows for i in range(servers)],
          f"shard row starts {starts}")
    after = _bytes_per_device()
    grew = {d: (after[d] - before[d]) if after[d] is not None else None
            for d in after}
    shard_bytes = shard_rows * DIM * 2
    check(all(g is None or g >= shard_bytes for g in grew.values()),
          f"a device did not take its {shard_bytes}-byte shard: {grew}")
    ids = np.arange(0, VOCAB, 997, dtype=np.int32)
    rows = np.ones((ids.size, DIM), np.float32)
    want = table.get()[ids].astype(np.float32) + 1.0
    table.add_rows(ids, rows)
    check(_bf16_close(table.get()[ids], want, np.abs(want), ulps=1.0),
          "add_rows on the sharded table disagrees with numpy")
    _report("sharded_table", mesh=label, servers=servers,
            shard_shape=[shard_rows, DIM], bytes_in_use_growth=grew,
            bytes_in_use=after)


def _dp_huffman():
    """Zipf word counts and their Huffman codes."""
    from multiverso_tpu.models.word2vec import build_huffman

    counts = np.maximum(1e7 / np.arange(1, VOCAB + 1), 1.0)
    return counts, build_huffman(counts)


def _dp_batches():
    rng = np.random.default_rng(SEED + 4)
    shape = (DP_DISPATCHES, DP_STEPS, DP_BATCH)
    return (rng.integers(0, VOCAB, shape).astype(np.int32),
            rng.integers(0, VOCAB, shape).astype(np.int32))


def _dp_model(mv, huffman, counts):
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    w_in = mv.create_table("matrix", VOCAB, DIM)
    w_out = mv.create_table("matrix", VOCAB, DIM)
    batch = DP_BATCH // 2 * mv.num_workers()
    cfg = Word2VecConfig(vocab_size=VOCAB, embedding_size=DIM, negative=0,
                         hs=True, batch_size=batch, init_lr=0.1, seed=5,
                         dp_sync="dispatch")
    return Word2Vec(cfg, w_in, w_out, counts=counts, huffman=huffman), \
        w_in, w_out


def _dp_init_state():
    rng = np.random.default_rng(SEED + 5)
    return ((rng.standard_normal((VOCAB, DIM)) * 0.1).astype(np.float32),
            np.zeros((VOCAB, DIM), np.float32))


def phase_dp_word2vec(mv, huffman, counts):
    """Two workers, ``dp_sync="dispatch"``: returns the tables after
    DP_DISPATCHES dispatches."""
    check(mv.num_workers() == 2, f"worker axis {mv.num_workers()}")
    t0 = time.perf_counter()
    model, w_in, w_out = _dp_model(mv, huffman, counts)
    check(model._dp_local() == 2, "the dispatch exchange is not in use")
    w0_in, w0_out = _dp_init_state()
    w_in.set_array(w0_in)
    w_out.set_array(w0_out)
    centers, contexts = _dp_batches()
    losses, times = [], []
    for d in range(DP_DISPATCHES):
        t_call = time.perf_counter()
        losses.append(float(model.train_batches(centers[d], contexts[d])))
        times.append(time.perf_counter() - t_call)
    check(bool(np.all(np.isfinite(losses))), f"dp loss not finite: {losses}")
    got = w_in.get(), w_out.get()
    _report("dp_word2vec", workers=2, losses=losses,
            setup_s=round(time.perf_counter() - t0 - sum(times[1:]), 3),
            steady_s=round(sum(times[1:]), 3))
    return got


def phase_dp_oracle(mv, huffman, counts, got) -> None:
    """One worker: each dispatch is the sum over the two workers of that
    worker's SEQUENTIAL local deltas on its batch columns
    (tests/test_word2vec.py's dispatch oracle, at full table width)."""
    check(mv.num_workers() == 1, f"worker axis {mv.num_workers()}")
    t0 = time.perf_counter()
    model, w_in, w_out = _dp_model(mv, huffman, counts)
    state = _dp_init_state()
    centers, contexts = _dp_batches()
    half = DP_BATCH // 2
    for d in range(DP_DISPATCHES):
        total = [np.zeros_like(state[0]), np.zeros_like(state[1])]
        for w in range(2):
            cols = slice(w * half, (w + 1) * half)
            w_in.set_array(state[0])
            w_out.set_array(state[1])
            model.train_batches(centers[d][:, cols], contexts[d][:, cols])
            total[0] += w_in.get() - state[0]
            total[1] += w_out.get() - state[1]
        state = (state[0] + total[0], state[1] + total[1])
    moved = float(np.abs(state[0] - _dp_init_state()[0]).max())
    check(moved > 100 * DP_ATOL, f"the oracle barely moved ({moved})")
    for name, a, b in (("w_in", got[0], state[0]),
                       ("w_out", got[1], state[1])):
        err = float(np.abs(a - b).max())
        check(err <= DP_ATOL, f"dp {name} differs from the one-worker "
              f"oracle by {err} > {DP_ATOL}")
    _report("dp_oracle", workers=1, max_moved=moved,
            seconds=round(time.perf_counter() - t0, 3))


def phase_decode_tp() -> None:
    import jax.numpy as jnp

    prompts = make_prompts(SERVE_REQUESTS)[:4]      # both reuses are in

    def compare(dtype, rung):
        """The same requests through a ``decode_tp=1`` and a
        ``decode_tp=2`` engine on one model."""
        lm = make_lm(jnp.dtype(dtype))
        runs = [serve(lm, prompts, f"lm-{rung}-tp{tp}", decode_tp=tp)
                for tp in (1, 2)]
        n_bad, first = mismatches(runs[1][0], runs[0][0])
        return n_bad, first, [r[1:] for r in runs]

    facts, engines = first_identical(
        "decode_tp", compare, "decode_tp=2 and decode_tp=1 outputs")
    (stats1, drift1, *secs1), (stats2, drift2, *secs2) = engines
    check_engine_stats(stats1, drift1)
    check_engine_stats(stats2, drift2)
    check(stats2["decode_tp"] == 2 and stats2["mesh_devices"] == 2,
          f"decode mesh: {stats2['decode_tp']}, {stats2['mesh_devices']}")
    check(stats2["kv_bytes_per_device"] * 2 == stats1["kv_bytes_per_device"],
          f"kv_bytes_per_device {stats2['kv_bytes_per_device']} is not "
          f"half of {stats1['kv_bytes_per_device']}")
    _report("decode_tp", requests=len(prompts),
            setup_s=[round(secs1[0], 3), round(secs2[0], 3)],
            steady_s=[round(secs1[1], 3), round(secs2[1], 3)],
            kv_bytes_per_device=[stats1["kv_bytes_per_device"],
                                 stats2["kv_bytes_per_device"]], **facts)


# -- driver ---------------------------------------------------------------------
def _device_facts():
    import jax

    first = jax.devices()[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(jax.devices())}


def _init(mv, *flags) -> dict:
    import jax

    mv.init(["chip_smoke", "-log_level=error", *flags])
    device = _device_facts()
    check(device["platform"] == "tpu",
          f"needs a TPU, found platform {device['platform']!r}")
    print(json.dumps({
        "device": device, "jax": jax.__version__,
        "compile_cache_dir": mv.session().compile_cache_dir,
        "mesh": {k: int(v) for k, v in mv.session().mesh.shape.items()},
    }), flush=True)
    return device


def run_one_chip() -> dict:
    mv = run_phase("import", importlib.import_module, "multiverso_tpu")
    device = run_phase("device", _init, mv)
    native_path = run_phase("native-build", build_native)
    run_phase("tables", phase_tables, mv)
    run_phase("word2vec", phase_word2vec, mv, native_path)
    lm = run_phase("lm", phase_lm)
    run_phase("serving", phase_serving, lm)
    run_phase("shutdown", mv.shutdown)
    return device


def run_four_chips() -> dict:
    mv = run_phase("import", importlib.import_module, "multiverso_tpu")
    device = run_phase("device", _init, mv, "-mesh_shape=2,2")
    run_phase("device", check, device["count"] == 4,
              f"--chips 4 found {device['count']} devices")
    counts, huffman = run_phase("dp_word2vec", _dp_huffman)
    run_phase("sharded_table", phase_sharded_table, mv, "worker=2,server=2")
    got = run_phase("dp_word2vec", phase_dp_word2vec, mv, huffman, counts)
    run_phase("shutdown", mv.shutdown)
    # what the two-worker run is compared with: one worker, and every
    # chip on the server axis (the default four-chip layout)
    run_phase("device", _init, mv, "-mesh_shape=1,4")
    run_phase("sharded_table", phase_sharded_table, mv, "worker=1,server=4")
    run_phase("dp_oracle", phase_dp_oracle, mv, huffman, counts, got)
    run_phase("decode_tp", phase_decode_tp)
    run_phase("shutdown", mv.shutdown)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the paths that exist across chips")
    args = ap.parse_args(argv)
    try:
        device = (run_four_chips if args.chips == 4 else run_one_chip)()
    except PhaseFailed as failed:
        # the one boundary: say which phase fell and why, then stop
        cause = failed.__cause__
        traceback.print_exception(cause)
        print(json.dumps({"ok": False, "phase": failed.phase,
                          "error": f"{type(cause).__name__}: {cause}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
