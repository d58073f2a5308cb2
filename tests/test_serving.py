"""Serving subsystem: batching triggers, shape buckets, snapshots, shedding.

The acceptance contract of the serving PR (docs/SERVING.md):

* deadline flush vs size flush — a partial batch waits exactly one
  deadline, a full batch goes immediately;
* shape-bucket reuse — repeated batch sizes pad to the same bucket and
  hit the warm jit cache (no recompile);
* snapshot consistency — replies computed while training Adds race are
  never torn, and the per-reply staleness bound is honored;
* load-shedding — past the queue-depth cap, submits fast-reject with the
  typed OverloadedError instead of queueing without bound.
"""

import threading
import time

import numpy as np
import pytest


class _Echo:
    """Minimal workload: no jit, no table — exercises the batcher alone."""

    source = (lambda: (None, 0), lambda: 0)

    def run(self, payloads, bucket, snap):
        return [p * 2 for p in payloads]


def _wait(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while not pred():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.005)


def test_deadline_flush_vs_size_flush(mv_session):
    from multiverso_tpu.serving import InferenceServer

    srv = InferenceServer("t")
    srv.register("echo", _Echo(), max_batch=8, deadline_ms=60.0,
                 max_queue=64)
    entry = srv._entry("echo")

    # partial batch: 3 requests sit until the OLDEST ages one deadline
    t0 = time.monotonic()
    futs = [srv.submit("echo", i) for i in range(3)]
    assert [f.result(timeout=5)["result"] for f in futs] == [0, 2, 4]
    waited = time.monotonic() - t0
    n, bucket, cause = entry.batcher.flushes[-1]
    assert (n, cause) == (3, "deadline")
    assert bucket == 4                      # 3 pads into the 4-bucket
    assert waited >= 0.055                  # held for the deadline

    # full batch: 8 requests flush on size, well before the deadline
    t0 = time.monotonic()
    futs = [srv.submit("echo", i) for i in range(8)]
    assert [f.result(timeout=5)["result"]
            for f in futs] == [2 * i for i in range(8)]
    waited = time.monotonic() - t0
    n, bucket, cause = entry.batcher.flushes[-1]
    assert (n, bucket, cause) == (8, 8, "size")
    assert waited < 0.055                   # did not wait out the deadline


def test_shape_bucket_reuse_no_recompile(mv_session):
    from multiverso_tpu.serving import EmbeddingNeighbors, InferenceServer

    table = mv_session.create_table("matrix", 64, 16, init_value="random")
    workload = EmbeddingNeighbors(table, k=4)
    srv = InferenceServer("t")
    srv.register("w2v", workload, max_batch=8, deadline_ms=5.0)
    entry = srv._entry("w2v")

    def flush_of(n):
        futs = [srv.submit("w2v", i) for i in range(n)]
        for f in futs:
            f.result(timeout=30)
        return entry.batcher.flushes[-1]

    assert flush_of(3)[1] == 4              # 3 -> bucket 4 (compiles once)
    warm = workload.jit_cache_size()
    for _ in range(3):                      # repeats reuse the SAME bucket
        assert flush_of(3)[1] == 4
    if warm >= 0:                           # cache introspection available
        assert workload.jit_cache_size() == warm, "bucket repeat recompiled"
    assert flush_of(7)[1] == 8              # new size -> new bucket, once
    grown = workload.jit_cache_size()
    assert flush_of(7)[1] == 8
    if grown >= 0:
        assert workload.jit_cache_size() == grown


def test_snapshot_consistency_under_concurrent_adds(mv_session):
    """Uniform whole-table Adds race the read path: any torn reply would
    mix values from two versions; the staleness bound must hold."""
    from multiverso_tpu.serving import InferenceServer

    rows, cols = 32, 16
    table = mv_session.create_table("matrix", rows, cols)
    bound = 0.1

    class Rows:
        source = table

        def run(self, payloads, bucket, snap):
            arr = np.asarray(snap.value)[:rows]     # logical rows
            return [arr[p] for p in payloads]

    srv = InferenceServer("t")
    srv.register("rows", Rows(), max_batch=4, deadline_ms=1.0,
                 max_staleness_s=bound)

    stop = threading.Event()

    def writer():
        delta = np.ones((rows, cols), np.float32)
        while not stop.is_set():
            table.add(delta)               # every element moves by 1 together

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    try:
        _wait(lambda: table.version >= 3)
        last_version = -1
        for i in range(60):
            reply = srv.predict("rows", i % rows, timeout_s=30)
            row = np.asarray(reply["result"])
            # consistent snapshot: the whole row is ONE version's value
            assert np.unique(row).size == 1, f"torn read: {row}"
            assert float(row[0]) == int(row[0])     # integer add count
            assert reply["staleness_s"] <= bound + 0.02
            assert reply["snapshot_version"] >= last_version
            last_version = reply["snapshot_version"]
    finally:
        stop.set()
        w.join(timeout=10)
    entry = srv._entry("rows")
    assert entry.manager.publishes >= 1


def test_load_shedding_at_queue_depth_cap(mv_session):
    from multiverso_tpu.serving import InferenceServer, OverloadedError

    started, release = threading.Event(), threading.Event()

    class Blocker:
        source = (lambda: (None, 0), lambda: 0)

        def run(self, payloads, bucket, snap):
            started.set()
            release.wait(timeout=30)
            return payloads

    srv = InferenceServer("t")
    srv.register("slow", Blocker(), max_batch=1, deadline_ms=0.1,
                 max_queue=3)
    first = srv.submit("slow", 0)
    started.wait(timeout=5)                 # worker is inside run_batch
    queued = [srv.submit("slow", i) for i in range(1, 4)]   # fills the cap
    with pytest.raises(OverloadedError) as exc:
        srv.submit("slow", 99)
    assert exc.value.depth == 3 and exc.value.cap == 3
    assert srv.stats("slow")["shed"] == 1
    release.set()
    assert first.result(timeout=10)["result"] == 0
    for f in queued:
        f.result(timeout=10)
    assert srv.stats("slow")["shed_rate"] > 0


def test_idle_server_never_wakes(mv_session):
    """The batcher's idle wait is UNTIMED: an idle registered model makes
    no flushes and its flush thread never wakes (the old 50 ms poll woke
    20x/s per model forever)."""
    from multiverso_tpu.serving import InferenceServer

    srv = InferenceServer("t")
    srv.register("echo", _Echo(), max_batch=8, deadline_ms=5.0)
    batcher = srv._entry("echo").batcher
    # settle: the thread is parked in the idle wait
    _wait(lambda: batcher._thread.is_alive())
    baseline = batcher.idle_wakeups
    time.sleep(0.3)                         # would be ~6 wakeups if polling
    assert batcher.idle_wakeups == baseline
    assert len(batcher.flushes) == 0
    # liveness after the untimed wait: submit still flushes, stop still
    # retires the thread
    assert srv.submit("echo", 21).result(timeout=5)["result"] == 42
    srv.stop()
    batcher._thread.join(timeout=5)
    assert not batcher._thread.is_alive()


def test_register_decoder_builds_engine_outside_registry_lock(mv_session):
    """Regression (locklint LK203, found by this PR's lint pass):
    DecodeEngine construction — the params replica copy plus the warmup
    compiles, seconds of work — used to run under the server's registry
    lock, wedging every submit() to every OTHER model behind one
    registration. Mid-construction, the other model must still serve."""
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving import server as server_mod

    srv = InferenceServer("t")
    srv.register("echo", _Echo(), max_batch=4, deadline_ms=5.0,
                 max_queue=64)
    entered, release = threading.Event(), threading.Event()

    class _SlowEngine:
        def __init__(self, name, lm, cfg):
            self.name = name
            entered.set()
            release.wait(10)

        def stop(self):
            pass

    real = server_mod.DecodeEngine
    server_mod.DecodeEngine = _SlowEngine
    try:
        t = threading.Thread(
            target=lambda: srv.register_decoder("slow-lm", object()))
        t.start()
        assert entered.wait(5), "registration never reached construction"
        fut = srv.submit("echo", 3)
        assert fut.result(timeout=5)["result"] == 6
        release.set()
        t.join(10)
        assert not t.is_alive()
        assert srv._entry("slow-lm").engine.name == "slow-lm"
    finally:
        server_mod.DecodeEngine = real


def test_lm_greedy_decode_matches_forward_oracle():
    """KV-cache decode == token-by-token full forward (pure function,
    ragged lengths in one right-padded batch)."""
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   forward, greedy_decode,
                                                   init_params)

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=16)
    params = init_params(cfg)
    rng = np.random.default_rng(0)
    lengths = np.array([6, 3], np.int32)
    toks = np.zeros((2, 6), np.int32)
    for b, l in enumerate(lengths):
        toks[b, :l] = rng.integers(1, cfg.vocab_size, l)
    new = 4
    out = np.asarray(greedy_decode(cfg, params, jnp.asarray(toks),
                                   jnp.asarray(lengths), new))
    for b in range(2):
        seq = list(toks[b, : lengths[b]])
        for t in range(new):
            logits = np.asarray(forward(
                cfg, params, jnp.asarray([seq], jnp.int32)))
            nxt = int(logits[0, -1].argmax())
            assert nxt == out[b, t], (b, t)
            seq.append(nxt)


def test_embedding_neighbors_matches_numpy_oracle(mv_session):
    from multiverso_tpu.serving import EmbeddingNeighbors, InferenceServer

    rows, dim, k = 48, 8, 5
    rng = np.random.default_rng(3)
    emb = rng.standard_normal((rows, dim)).astype(np.float32)
    table = mv_session.create_table("matrix", rows, dim, init_value=emb)
    srv = InferenceServer("t")
    srv.register("w2v", EmbeddingNeighbors(table, k=k), max_batch=4,
                 deadline_ms=1.0)
    normed = emb / np.linalg.norm(emb, axis=1, keepdims=True)
    for q in (0, 7, 31):
        ids, scores = srv.predict("w2v", q, timeout_s=30)["result"]
        sims = normed @ normed[q]
        sims[q] = -np.inf
        expect = np.argsort(-sims)[:k]
        np.testing.assert_array_equal(np.asarray(ids), expect)
        np.testing.assert_allclose(np.asarray(scores), sims[expect],
                                   rtol=1e-4, atol=1e-5)


def test_histogram_percentiles():
    from multiverso_tpu.dashboard import Histogram

    h = Histogram("t", window=128, register=False)
    for v in range(1, 101):                 # 1..100 ms
        h.record(float(v))
    assert h.percentile(50) == pytest.approx(50, abs=1)
    assert h.percentile(99) == pytest.approx(99, abs=1)
    s = h.summary()
    assert s["count"] == 100 and s["p50_ms"] <= s["p99_ms"]


def test_derived_cache_single_compute_under_concurrent_readers():
    """DerivedCache.get is atomic across a version change: two readers
    racing the same fresh snapshot must produce ONE fn() computation
    (the un-locked check-then-act used to let both miss and recompute —
    a doubled replica copy exactly at the publish spike)."""
    from multiverso_tpu.serving.snapshot import DerivedCache, Snapshot

    calls = []

    def fn(value):
        calls.append(threading.current_thread().name)
        time.sleep(0.05)            # widen the miss window
        return value * 2

    cache = DerivedCache(fn)
    snap = Snapshot(21, 7, 0.0)
    results = [None, None]
    barrier = threading.Barrier(2)

    def reader(ix):
        barrier.wait()
        results[ix] = cache.get(snap)

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert results == [42, 42]
    assert len(calls) == 1, f"fn computed {len(calls)}x for one version"
    # and a later version recomputes exactly once more
    assert cache.get(Snapshot(30, 8, 0.0)) == 60
    assert len(calls) == 2


def test_register_decoder_losing_race_to_stop_stops_the_engine(
        mv_session, monkeypatch):
    """Regression: register_decoder's post-construction re-check only
    looked for a duplicate name — a server.stop() landing during the
    (outside-the-lock, seconds-long) engine construction left a live
    engine registered on a stopped server, its decode loop outliving
    the 'serving drains first' teardown."""
    from multiverso_tpu.log import FatalError
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving import server as server_mod

    building, release = threading.Event(), threading.Event()
    stopped = []

    class _StubEngine:
        def __init__(self, name, lm, cfg):
            self.name = name
            building.set()
            release.wait(10)

        def stop(self):
            stopped.append(self.name)

    monkeypatch.setattr(server_mod, "DecodeEngine", _StubEngine)
    srv = InferenceServer("t")
    result = []

    def register():
        try:
            srv.register_decoder("lm", object(), slots=2, max_prompt=4,
                                 max_new=4)
        except FatalError as exc:
            result.append(str(exc))

    t = threading.Thread(target=register)
    t.start()
    try:
        assert building.wait(5), "construction never started"
        srv.stop()                       # lands mid-construction
        release.set()
        t.join(10)
    finally:
        release.set()
        t.join(10)
    assert not t.is_alive()
    assert result and "stopped during" in result[0]
    assert stopped == ["lm"], "racing engine was never stopped"
    assert "lm" not in srv._models
