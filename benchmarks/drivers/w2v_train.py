"""Driver ``w2v_train``: ``Word2Vec.train_device_steps`` back to back on
two ``mv.create_table("matrix", ...)`` tables, on one chip or, with the
traffic file's ``mv_flags`` and ``workers``, over a (worker, server)
mesh.

Set-up builds ONE trainer, drives it through its first
``check_dispatches`` dispatches with the window's own call, takes the
program's readings for ``correct`` there (losses, pairs counted, the
norms of each table's change after the first dispatch and after the
last), and hands the same trainer to the window.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks import gen, tracered
from benchmarks.harness import load_module


def build(ctx, mv):
    import jax.numpy as jnp

    from multiverso_tpu.apps.wordembedding import subsample_probs
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    cfg, workers = ctx.config, int(ctx.traffic.get("workers", 1))
    V, D = cfg["vocab_size"], cfg["embedding_size"]
    dtype = jnp.dtype(cfg["table_dtype"])
    if mv.num_workers() != workers:
        raise SystemExit(f"w2v_train: the mesh has {mv.num_workers()} "
                         f"workers, the traffic file says {workers}")
    counts = gen.w2v_counts(V, cfg["total_words"])
    discard = subsample_probs(counts, cfg["sample"]).astype(np.float32)
    ctx.mark("laws")
    w_in = mv.create_table("matrix", V, D, dtype=dtype)
    w_out = mv.create_table("matrix", V, D, dtype=dtype)
    if w_in.padded_shape != (V, D):
        raise SystemExit(f"w2v_train: {V} rows do not divide over the "
                         f"server axis (padded {w_in.padded_shape})")
    init = gen.w2v_init_table(ctx.seed, (V, D), dtype)
    w_in.set_array(init)
    del init
    ctx.mark("tables")
    model = Word2Vec(Word2VecConfig(
        vocab_size=V, embedding_size=D, window=cfg["window"],
        negative=cfg["negative"], init_lr=cfg["init_lr"],
        batch_size=cfg["batch_size_per_worker"] * workers,
        oversample=cfg["oversample"], neg_pool_size=cfg["neg_pool_size"],
        row_mean_updates=cfg["row_mean_updates"],
        row_mean_static=cfg["row_mean_static"],
        row_update_cap=cfg["row_update_cap"],
        shared_negatives=cfg["shared_negatives"], seed=cfg["trainer_seed"],
        **ctx.traffic.get("trainer", {})), w_in, w_out, counts=counts)
    if workers > 1 and model._dp_local() != workers:
        raise SystemExit("w2v_train: the dispatch exchange is not in use")
    model.total_words = cfg["total_words"]
    ctx.mark("trainer")
    ids, sents = gen.w2v_corpus(ctx.seed, cfg["corpus_words"], V,
                                cfg["sentence_words"])
    model.load_corpus_chunk(ids, sents, discard)
    del ids, sents
    ctx.mark("chunk")

    # the first dispatches: the window's own call, and the readings
    def norms():
        a0 = gen.w2v_init_table(ctx.seed, (V, D), dtype)
        return [float(x) for x in _change_fn()(w_in.array, a0, w_out.array)]

    steps = cfg["steps_per_dispatch"]
    n = int(ctx.traffic["check_dispatches"])
    got = {"loss": [], "pairs": [], "first": None, "last": None}
    for d in range(n):
        loss, count = model.train_device_steps(steps)
        got["loss"].append(float(loss))
        got["pairs"].append(float(count))
        if d == 0:
            got["first"] = norms()
        if d == n - 1:
            got["last"] = norms()
        ctx.mark(f"dispatch{d + 1}")
    return {"model": model, "tables": (w_in, w_out), "got": got,
            "steps": steps}


@functools.cache
def _change_fn():
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    return jax.jit(lambda a, a0, b: (
        norm(a.astype(jnp.float32) - a0.astype(jnp.float32)), norm(b)))


def window(state, ctx, seconds: float) -> dict:
    """Dispatches back to back: dispatch i is sent, then the count and
    loss of dispatch i-1 are fetched, so the device always has one
    queued and the host never runs ahead by more. The clock closes on
    the fetch of the last dispatch's count."""
    model, steps = state["model"], state["steps"]
    pairs, losses, pending = 0.0, [], None
    t0 = time.perf_counter()
    while True:
        with tracered.span("dispatch"):
            sent = model.train_device_steps(steps)
        if pending is not None:
            with tracered.span("fetch"):
                losses.append(float(pending[0]))
                pairs += float(pending[1])
        pending = sent
        if time.perf_counter() - t0 >= seconds:
            break
    with tracered.span("fetch"):
        losses.append(float(pending[0]))
        pairs += float(pending[1])
    elapsed = time.perf_counter() - t0
    ctx.counters.update(
        attempted=len(losses),
        failed=int(np.sum(~np.isfinite(losses))),
        dispatches=len(losses), steps=len(losses) * steps, pairs=pairs,
        elapsed_s=elapsed, last_loss=losses[-1])
    return {"train_pairs_per_s": pairs / elapsed}


def release(state, ctx) -> dict:
    state.pop("model")
    state.pop("tables")
    return state["got"]


def compare(got: dict, ref: dict, limits: dict) -> list:
    """Each number compared, beside its limit (``traffic.limits``)."""
    def rel(a, b):
        return abs(a - b) / max(abs(b), 1e-30)

    def worst_leaf(a, b):
        # the gap between the two norms of a leaf, against the
        # reference's norm of that leaf or of the median leaf
        floor = float(np.median(b))
        return max(abs(x - y) / max(y, floor, 1e-30) for x, y in zip(a, b))

    rows = [
        ("loss_gap", max(rel(a, b) for a, b in zip(got["loss"], ref["loss"]))),
        ("pairs_gap", max(abs(a - b) for a, b in
                          zip(got["pairs"], ref["pairs"]))),
        ("first_update_norm_gap", worst_leaf(got["first"], ref["first"])),
        ("change_norm_gap", worst_leaf(got["last"], ref["last"])),
    ]
    rows = [(n, v if np.isfinite(v) else None) for n, v in rows]
    return [{"name": n, "value": v, "limit": limits[n]} for n, v in rows]


def check(got: dict, ctx) -> list:
    ref = load_module("reference", ctx.config_name).follow(
        ctx.config, ctx.seed, len(got["loss"]),
        workers=int(ctx.traffic.get("workers", 1)))
    ctx.counters["reference"] = ref
    ctx.counters["program"] = got
    return compare(got, ref, ctx.traffic["limits"])


def controls(got: dict, ctx) -> dict:
    """The reference put in the program's place, held against the sound
    reference: tables in an 8-bit float (the control: the nearest
    precision below bfloat16), and the planted faults."""
    ref = load_module("reference", ctx.config_name)
    sound = ctx.counters["reference"]
    workers = int(ctx.traffic.get("workers", 1))

    def against(**kw):
        return compare(ref.follow(ctx.config, ctx.seed, len(got["loss"]),
                                  workers=workers, **kw),
                       sound, ctx.traffic["limits"])

    return {"control_float8_e5m2": against(store="float8_e5m2"),
            "fault_half_batch": against(fault="half_batch")}
