"""Overload-graceful serving: priority scheduling, deadlines, preemption.

The acceptance contract of the overload PR (docs/SERVING.md, "Overload
and preemption"):

* **preemption is invisible in the tokens** — with a pool sized to
  force preemptions, every request's output is bit-identical to its
  no-pressure ``greedy_decode`` oracle (recompute-from-prompt+emitted
  resumes exactly where the victim stopped), the one-trace invariant
  holds, and the pool's books balance after EVERY preemption;
* **no starvation** — under sustained top-class load, a class-0
  request still completes (the stride scheduler's weighted-fair share
  is positive for every class);
* **no livelock** — two oversized requests cannot preempt each other
  forever: the oldest-live floor plus the per-request preemption
  budget (pessimistic re-admission once spent) bound the churn;
* **deadlines fail fast** — an expired request is dropped at queue-POP
  time with ``DeadlineExceededError`` and burns ZERO prefill;
* **sheds carry the retry policy** — ``OverloadedError.retriable`` is
  False exactly when retrying can never help (request bigger than the
  whole pool).
"""

import threading
import time

import numpy as np
import pytest


def _small_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=48)
    base.update(kw)
    return TransformerConfig(**base)


def _oracle(cfg, params, prompt, max_new, eos_id=None):
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import greedy_decode

    out = np.asarray(greedy_decode(
        cfg, params, jnp.asarray(prompt[None]),
        jnp.asarray([len(prompt)]), max_new, eos_id))[0]
    if eos_id is not None:
        hits = np.nonzero(out == eos_id)[0]
        if hits.size:
            return out[: hits[0] + 1]
    return out


# -- the preemption oracle ----------------------------------------------------

@pytest.mark.parametrize("prefix,spec_k", [(True, 0), (False, 0),
                                           (True, 2)])
def test_preemption_oracle_bit_identical(mv_session, prefix, spec_k):
    """Seeded churn trace against a pool sized to FORCE preemptions:
    every output equals the un-preempted greedy oracle, the fused step
    and chunk programs stay at one compiled trace each, and the pool's
    invariants hold after every single preemption (``drift()`` asserted
    inside a wrapped ``_preempt``)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    params, _ = lm.snapshot_params()
    srv = InferenceServer("t")
    # 4 slots x optimistic 2-block prompt reservations fill the 8-block
    # pool exactly; every generation then crosses block boundaries, so
    # growth MUST preempt (asserted below — a quiet run proves nothing)
    engine = srv.register_decoder(
        "lm", lm, slots=4, max_prompt=8, max_new=16, kv_block_size=4,
        kv_pool_blocks=8, prefill_token_budget=4, prefix_cache=prefix,
        spec_k=spec_k, max_queue=64)
    engine.warmup()

    drift_after_preempt = []
    orig = engine._preempt

    def checked(req, why=""):
        orig(req, why)
        drift_after_preempt.append(engine._pool.drift())

    engine._preempt = checked

    rng = np.random.default_rng(23)
    reqs, futs = [], []
    for _ in range(14):
        plen = int(rng.integers(4, 9))
        prompt = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        max_new = int(rng.integers(8, 17))
        reqs.append((prompt, max_new))
        futs.append(srv.submit("lm", {"prompt": prompt,
                                      "max_new": max_new,
                                      "priority": int(rng.integers(0, 3))}))
    for (prompt, max_new), fut in zip(reqs, futs):
        reply = fut.result(timeout=180)
        np.testing.assert_array_equal(
            reply["result"], _oracle(cfg, params, prompt, max_new),
            err_msg=f"prompt {prompt} max_new {max_new} "
                    f"(prefix={prefix}, spec_k={spec_k})")
    stats = engine.stats()
    assert stats["preemptions"] > 0, "pool never pressured; geometry bug"
    assert stats["preempted"] > 0
    assert all(msg is None for msg in drift_after_preempt), \
        drift_after_preempt
    assert stats["step_traces"] == 1
    assert stats["prefill_traces"] == 1
    assert stats["completed"] == len(reqs)
    assert stats["kv_blocks_live"] == 0
    engine._pool.check()


def test_livelock_two_oversized_requests_terminate(mv_session):
    """Two requests whose worst case each exceeds half the pool cannot
    preempt each other forever: the oldest-live floor means the older
    one is never evicted, and the younger one's budget runs out into a
    pessimistic (full-reservation) re-admission that simply waits.
    Both complete, bit-identically, with bounded churn."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    params, _ = lm.snapshot_params()
    srv = InferenceServer("t")
    # worst case ceil((8 + 16) / 4) = 6 blocks per request > 8 / 2
    engine = srv.register_decoder(
        "lm", lm, slots=2, max_prompt=8, max_new=16, kv_block_size=4,
        kv_pool_blocks=8, prefill_token_budget=4, preempt_budget=3)
    engine.warmup()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    futs = [srv.submit("lm", {"prompt": p, "max_new": 16})
            for p in prompts]
    for p, fut in zip(prompts, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=180)["result"],
            _oracle(cfg, params, p, 16))
    stats = engine.stats()
    assert stats["preemptions"] > 0
    # the capacity optimistic admission buys: two worst cases (6 + 6
    # blocks) never fit the 8-block pool together, two prompt
    # reservations (2 + 2) do, so both sequences were live at once
    assert stats["peak_live_seqs"] == 2
    # churn bound: each preemption burns budget, and a spent budget
    # means pessimistic re-admission (no further churn possible)
    assert stats["preemptions"] <= 2 * (3 + 1)
    assert stats["kv_blocks_live"] == 0
    engine._pool.check()


def test_starvation_bound_low_priority_completes(mv_session):
    """A single class-0 request under a sustained class-7 flood still
    completes BEFORE the flood drains: stride scheduling gives every
    non-empty lane a positive admission share (weight 2**p), so the
    low lane is served as soon as the top lane's pass overtakes it —
    strict priority would leave it for last."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=2, max_prompt=8, max_new=8, kv_block_size=4,
        prefill_token_budget=4, max_queue=64)
    engine.warmup()
    rng = np.random.default_rng(11)
    order, lock = [], threading.Lock()

    def tag(label):
        def cb(_f):
            with lock:
                order.append(label)
        return cb

    flood = []
    for i in range(12):
        p = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
        f = srv.submit("lm", {"prompt": p, "max_new": 8, "priority": 7})
        f.add_done_callback(tag(f"hi{i}"))
        flood.append(f)
    low_fut = srv.submit("lm", {"prompt": rng.integers(
        1, cfg.vocab_size, 6).astype(np.int32),
        "max_new": 8, "priority": 0})
    low_fut.add_done_callback(tag("low"))
    low_fut.result(timeout=120)
    for f in flood:
        f.result(timeout=120)
    with lock:
        low_at = order.index("low")
    assert low_at < len(flood), \
        f"class-0 request starved to the very end: {order}"


# -- deadlines ----------------------------------------------------------------

def test_deadline_dropped_at_pop_burns_no_prefill(mv_session):
    """Requests whose deadline expires while queued behind a busy slot
    fail with DeadlineExceededError at pop time — counted in
    ``deadline_drops``/DEADLINE_DROPS — and the engine never prefills
    a single one of their tokens (the fix: the pre-PR engine ran the
    FULL prefill before anything checked anything)."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import DeadlineExceededError, InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=1, max_prompt=8, max_new=24, kv_block_size=4,
        prefill_token_budget=4, max_queue=16)
    engine.warmup()
    # slow each fused step a touch: the tiny test model otherwise
    # drains its 24 iterations inside the doomed requests' deadlines
    # and the slot frees before they expire (flaky geometry)
    orig_step = engine._step_fn

    def slow_step(*a, **kw):
        time.sleep(0.003)
        return orig_step(*a, **kw)

    engine._step_fn = slow_step
    rng = np.random.default_rng(3)
    p0 = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    occupant = srv.submit("lm", {"prompt": p0, "max_new": 24})
    deadline = time.monotonic() + 10
    while not engine._active.any():
        assert time.monotonic() < deadline
        time.sleep(0.002)
    doomed = [srv.submit("lm", {"prompt": p0, "max_new": 4,
                                "deadline_s": 0.005})
              for _ in range(3)]
    occupant.result(timeout=120)
    for fut in doomed:
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=60)
    engine._step_fn = orig_step      # stats() reads its jit cache size
    stats = engine.stats()
    assert stats["deadline_drops"] == 3
    snap = Dashboard.snapshot()
    assert snap["DEADLINE_DROPS[lm]"]["value"] >= 3
    # only the occupant's prompt ever prefilled
    assert engine.prefill_tokens == len(p0)
    assert stats["completed"] == 1


def test_submit_validates_priority_and_deadline(mv_session):
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    srv.register_decoder("lm", lm, slots=1, max_prompt=4, max_new=4,
                         kv_block_size=4, prefill_token_budget=4)
    p = np.ones(2, np.int32)
    with pytest.raises(ValueError):
        srv.submit("lm", {"prompt": p, "priority": 9})
    with pytest.raises(ValueError):
        srv.submit("lm", {"prompt": p, "priority": -1})
    with pytest.raises(ValueError):
        srv.submit("lm", {"prompt": p, "deadline_s": 0.0})


# -- retriable sheds ----------------------------------------------------------

def test_overloaded_retriable_hint(mv_session):
    """Queue-cap sheds are retriable (capacity frees as requests
    complete); a request bigger than the whole pool is NOT (no amount
    of waiting ever admits it)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer, OverloadedError

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=1, max_prompt=4, max_new=8, kv_block_size=4,
        kv_pool_blocks=2, max_queue=2, preempt=False)
    engine.warmup()
    rng = np.random.default_rng(8)
    big = rng.integers(1, cfg.vocab_size, 4).astype(np.int32)
    with pytest.raises(OverloadedError) as exc:
        srv.submit("lm", {"prompt": big, "max_new": 8})
    assert exc.value.retriable is False        # permanent: never fits
    small = rng.integers(1, cfg.vocab_size, 2).astype(np.int32)
    futs, shed = [], None
    for _ in range(8):
        try:
            futs.append(srv.submit("lm", {"prompt": small, "max_new": 4}))
        except OverloadedError as e:
            shed = e
            break
    assert shed is not None and shed.retriable is True   # transient
    for f in futs:
        f.result(timeout=120)


# -- the scheduler itself -----------------------------------------------------

def test_prio_queue_weighted_fair_and_lookahead(mv_session):
    from multiverso_tpu.serving.decode_engine import _PrioQueue, _Request

    def req(priority, deadline=None):
        return _Request(np.ones(2, np.int32), 4, priority=priority,
                        deadline=deadline)

    # weighted-fair: 4 class-2 + 4 class-0 pops interleave 4:1 (stride
    # weight 2**p), ties to the higher class — NOT strict priority
    q = _PrioQueue("t", lookahead=4)
    for _ in range(4):
        q.append(req(2))
    for _ in range(4):
        q.append(req(0))
    now = time.monotonic()
    got = []
    while len(q):
        r, expired = q.pop_admissible(now, lambda r: True)
        assert expired == []
        got.append(r.priority)
    assert got == [2, 0, 2, 2, 2, 0, 0, 0]

    # bounded lookahead: the starved head is bypassed at most
    # `lookahead` times, then admission waits for it
    q = _PrioQueue("t", lookahead=2)
    head = req(1)
    others = [req(1) for _ in range(3)]
    q.append(head)
    for r in others:
        q.append(r)
    covers = lambda r: r is not head
    first, _ = q.pop_admissible(now, covers)
    assert first is others[0] and head.skips == 1
    second, _ = q.pop_admissible(now, covers)
    assert second is others[1] and head.skips == 2
    blocked, _ = q.pop_admissible(now, covers)
    assert blocked is None            # bypass budget spent: head waits
    unblocked, _ = q.pop_admissible(now, lambda r: True)
    assert unblocked is head

    # expired requests drop at pop wherever the scan touches them
    q = _PrioQueue("t", lookahead=4)
    dead1, live, dead2 = (req(1, deadline=now - 1.0), req(1),
                          req(1, deadline=now - 2.0))
    for r in (dead1, live, dead2):
        q.append(r)
    got, expired = q.pop_admissible(now, lambda r: True)
    assert got is live
    assert set(expired) == {dead1}   # head sweep; dead2 still queued
    got2, expired2 = q.pop_admissible(now, lambda r: True)
    assert got2 is None and expired2 == [dead2]
    assert len(q) == 0

    # preempted re-enqueue lands at the FRONT of its lane
    q = _PrioQueue("t", lookahead=0)
    a, b = req(1), req(1)
    q.append(a)
    q.appendleft(b)
    first, _ = q.pop_admissible(now, lambda r: True)
    assert first is b

    # the bypass bound is GLOBAL: a starved head accumulates skips
    # from OTHER lanes' admissions too, and at the bound it freezes
    # every lane until it fits (freed blocks must accumulate for it —
    # per-lane-only accounting would let optimistic admissions starve
    # a pessimistic waiter forever)
    q = _PrioQueue("t", lookahead=2)
    head0 = req(0)                  # the never-coverable waiter
    q.append(head0)
    for _ in range(4):
        q.append(req(2))
    covers = lambda r: r is not head0
    got1, _ = q.pop_admissible(now, covers)       # p2 wins the tie;
    assert got1.priority == 2 and head0.skips == 0    # head0 unchecked
    got2, _ = q.pop_admissible(now, covers)       # p0 scanned first now
    assert got2.priority == 2 and head0.skips == 1
    got3, _ = q.pop_admissible(now, covers)
    assert got3.priority == 2 and head0.skips == 2
    frozen2, _ = q.pop_admissible(now, covers)
    assert frozen2 is None           # p2 still has work, but is FROZEN
    thaw, _ = q.pop_admissible(now, lambda r: True)
    assert thaw is head0             # the starved head goes through first
    resumed, _ = q.pop_admissible(now, covers)
    assert resumed is not None and resumed.priority == 2


def test_pin_holds_while_preempted_request_waits(mv_session):
    """A preempted request awaiting resume EXTENDS the snapshot pin
    across the eviction gap: training can publish, but the engine
    refuses to move its pin while the resume queue is non-empty (the
    recompute is only bit-identical under the first life's params) —
    and moves it again the moment the queue empties."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.decode_engine import _Request

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=2, max_prompt=8, max_new=8, kv_block_size=4,
        prefill_token_budget=4, max_staleness_s=0.0)
    engine.warmup()
    v0 = engine._pinned_version
    # a fabricated preempted waiter at the front of its lane (the loop
    # stays asleep: nothing notifies, and the cleared free-slot set
    # keeps a spurious wake from admitting it)
    saved_slots = list(engine._free_q)
    engine._free_q.clear()
    waiter = _Request(np.ones(4, np.int32), 8)
    waiter.out = [1, 2]
    waiter.resumed = True
    waiter.preempts = 1
    with engine._cv:
        engine._q.appendleft(waiter)
    assert engine._q.n_resumed == 1
    rng = np.random.default_rng(2)
    lm.train_batch(rng.integers(0, cfg.vocab_size,
                                (2, 12)).astype(np.int32))
    engine._maybe_refresh()
    assert engine._pinned_version == v0     # held for the waiter
    with engine._cv:
        popped, _ = engine._q.pop_admissible(time.monotonic(),
                                             lambda r: True)
    assert popped is waiter and engine._q.n_resumed == 0
    engine._maybe_refresh()
    assert engine._pinned_version is not None
    assert engine._pinned_version > v0      # released: pin moves again
    engine._free_q.extend(saved_slots)


def test_squeeze_raced_reserve_requeues_without_double_count(mv_session):
    """A pool squeeze racing an admission between the coverage gate and
    the reservation must REQUEUE the request (not kill the loop), give
    every claimed block back, and count the prefix hits exactly once —
    on the re-admission that actually stands."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.decode_engine import _Request

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    params, _ = lm.snapshot_params()
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=2, max_prompt=8, max_new=8, kv_block_size=4,
        kv_pool_blocks=6, prefill_token_budget=4)
    engine.warmup()
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    srv.submit("lm", {"prompt": prompt, "max_new": 8}).result(timeout=120)
    assert engine._pool.n_cached == 2       # both full prompt blocks
    # hold every FREE block so the full-hit CoW alloc must raise (the
    # matched cached blocks reactivate at lookup, leaving free==0)
    free = engine._pool.n_free
    assert engine.squeeze_pool(free / engine._pool.capacity) == free
    assert engine._pool.n_free == 0
    hits0 = engine.prefix_hits
    req = _Request(prompt, 8)
    slot = engine._free_q.popleft()
    engine._begin_prefill(req, slot)        # raises inside -> requeues
    assert req.slot == -1 and req.blocks == []
    assert len(engine._q) == 1
    assert slot in engine._free_q
    assert engine.prefix_hits == hits0      # failed attempt: no count
    assert engine._pool.n_cached == 2       # claimed blocks returned
    assert engine._pool.drift() is None
    engine.unsqueeze_pool()
    with engine._cv:
        engine._cv.notify()                 # loop picks the requeue up
    out = req.future.result(timeout=120)["result"]
    np.testing.assert_array_equal(out, _oracle(cfg, params, prompt, 8))
    assert engine.prefix_hits == hits0 + 2  # counted exactly once
    engine._pool.check()


# -- chaos kinds --------------------------------------------------------------

def test_fault_plan_burst_and_pool_squeeze_grammar(mv_session):
    from multiverso_tpu.serving import FaultPlan

    plan = FaultPlan("burst=2:3, pool_squeeze=1:0.5:4")
    assert (plan.burst_at, plan.burst_count) == (2, 3)
    assert plan.squeeze_at == 1
    assert plan.squeeze_fraction == 0.5
    assert plan.squeeze_release_at == 4
    assert plan.active()
    assert plan.burst_n(1) == 0 and plan.burst_n(2) == 3
    assert plan.squeeze_frac(1) == 0.5 and plan.squeeze_frac(2) is None
    assert not plan.squeeze_release(3) and plan.squeeze_release(4)
    assert plan.counts["bursts"] == 1
    assert plan.counts["pool_squeezes"] == 1
    assert FaultPlan("pool_squeeze=3:0.25").squeeze_release_at == 0
    for bad in ("burst=0:3", "burst=2:0", "pool_squeeze=0:0.5",
                "pool_squeeze=2:1.5", "pool_squeeze=2:0.5:1"):
        with pytest.raises(ValueError):
            FaultPlan(bad)


def test_squeeze_pool_forces_preemption_and_stays_drift_clean(mv_session):
    """engine.squeeze_pool holds blocks hostage (pool_drift must NOT
    read them as a leak), forces preemption churn on live traffic, and
    unsqueeze/stop return every block — outputs stay oracle-exact
    throughout."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    params, _ = lm.snapshot_params()
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=4, max_prompt=8, max_new=12, kv_block_size=4,
        kv_pool_blocks=12, prefill_token_budget=4, max_queue=32)
    engine.warmup()
    held = engine.squeeze_pool(0.5)
    assert held == 6
    assert engine.pool_drift() is None        # a squeeze is not a leak
    rng = np.random.default_rng(31)
    reqs, futs = [], []
    for _ in range(8):
        prompt = rng.integers(1, cfg.vocab_size,
                              int(rng.integers(4, 9))).astype(np.int32)
        reqs.append(prompt)
        futs.append(srv.submit("lm", {"prompt": prompt, "max_new": 12}))
    for prompt, fut in zip(reqs, futs):
        np.testing.assert_array_equal(
            fut.result(timeout=180)["result"],
            _oracle(cfg, params, prompt, 12))
    assert engine.stats()["preemptions"] > 0
    assert engine.unsqueeze_pool() == 6
    assert engine.stats()["kv_blocks_live"] == 0
    engine._pool.check()


# -- observability ------------------------------------------------------------

def test_preempt_spans_stats_and_trace_summary_column(mv_session):
    """decode.preempt spans carry victim/blocks-freed attrs, the
    resume's decode.admit span carries the running ``preempted``
    count, and tools/trace_summary's per-request report ships the
    ``preempted`` column for exactly those rows."""
    import json

    from multiverso_tpu import trace
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from tools.trace_summary import load_host_spans, request_report

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm", lm, slots=4, max_prompt=8, max_new=16, kv_block_size=4,
        kv_pool_blocks=8, prefill_token_budget=4, max_queue=32)
    engine.warmup()
    rng = np.random.default_rng(41)
    trace.enable(65536)
    try:
        futs = []
        for _ in range(10):
            prompt = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
            futs.append(srv.submit("lm", {"prompt": prompt,
                                          "max_new": 16}))
        for f in futs:
            f.result(timeout=180)
        spans = trace.collector().spans()
        doc = trace.export_chrome()
    finally:
        trace.disable()
        trace.collector().clear()
    assert engine.stats()["preemptions"] > 0
    preempts = [sp for sp in spans if sp.name == "decode.preempt"]
    assert preempts, "no decode.preempt span recorded"
    for sp in preempts:
        assert "victim" in sp.attrs and "blocks_freed" in sp.attrs
        assert sp.attrs["preempts"] >= 1
    admits = [sp for sp in spans if sp.name == "decode.admit"
              and "preempted" in sp.attrs]
    assert admits, "no resume admission annotated"
    rows = request_report(load_host_spans_doc(doc))
    assert any(r.get("preempted") for r in rows)


def load_host_spans_doc(doc):
    """Chrome doc -> trace_summary spans, without a temp file."""
    import json
    import tempfile

    from tools.trace_summary import load_host_spans

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(doc, f)
        path = f.name
    return load_host_spans(path)
