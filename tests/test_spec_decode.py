"""Speculative decoding: n-gram prompt-lookup drafting + fixed-K verify.

The acceptance contract of the spec-decode PR (docs/SERVING.md,
"Speculative decoding"):

* **token identity** — for a randomized trace (repetitive AND
  non-repetitive prompts, an exact full-hit repeat, eos truncation),
  a ``spec_k > 0`` engine's outputs are token-for-token the
  ``spec_k=0`` engine's and the per-request ``greedy_decode`` oracle's:
  speculation changes the schedule, never the tokens. Covered with the
  prefix cache on and off, and with a prompt in several chunks and in one
  (``decode_tp=2`` rides in tests/test_sharded_decode.py);
* **one trace each** — exactly one compiled step + one verify trace
  (+ one chunk / one CoW where applicable) per engine config, with
  ``decode_step_retraces == 0``: K is the only new static, drafts and
  the accepted length are data;
* **multi-token metrics** — ITL is recorded per EMITTED token (the
  step interval divides across the window's emissions), DECODE_TOKENS
  counts every accepted token, and ``decode.iter`` carries the
  ``accepted`` attr — while a ``spec_k=0`` engine's metrics surface is
  byte-for-byte today's (no spec stats keys, no SPEC_* counters, flat
  spans).
"""

import time

import numpy as np
import pytest

from multiverso_tpu import trace


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


def _spec_trace(rng, vocab, max_prompt, max_new, n=10):
    """Mixed trace: motif-tiled (repetitive — the drafter's regime) and
    fully random prompts, plus an exact repeat of the first prompt (the
    full-hit path when the prefix cache is on)."""
    reqs = []
    for i in range(n):
        plen = int(rng.integers(2, max_prompt + 1))
        if i % 3 == 2:
            prompt = rng.integers(1, vocab, plen).astype(np.int32)
        else:
            motif = rng.integers(1, vocab,
                                 int(rng.integers(2, 5))).astype(np.int32)
            prompt = np.tile(motif, -(-plen // len(motif)))[:plen]
        reqs.append((prompt.astype(np.int32),
                     int(rng.integers(2, max_new + 1))))
    # block-aligned exact repeat (8 = 2 x kv_block_size 4): a FULL
    # prefix-cache hit whose first fused step is a speculative window
    reqs.append((reqs[0][0][:8] if len(reqs[0][0]) >= 8
                 else np.tile(reqs[0][0], 8)[:8].astype(np.int32),
                 max_new))
    reqs.append((reqs[-1][0].copy(), max_new))
    return reqs


@pytest.mark.parametrize("budget,prefix", [(4, True), (4, False),
                                           (12, False)])
def test_spec_matches_baseline_and_oracle(mv_session, budget, prefix):
    """The correctness oracle: spec_k=3 outputs are token-identical to
    the spec_k=0 engine AND the per-request greedy oracle — prefix
    cache on/off, prompts in chunks of 4 and whole in one chunk
    (budget 12 = ``max_prompt``) — while the engine actually speculates
    (accepted > 0) and the compiled-trace set stays at one step + one verify (+ one
    chunk / one CoW)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.workloads import _jit_cache_size

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engines = {
        k: srv.register_decoder(
            f"lm_k{k}", lm, slots=4, max_prompt=12, max_new=10,
            kv_block_size=4, prefill_token_budget=budget,
            prefix_cache=prefix, spec_k=k)
        for k in (3, 0)
    }
    for e in engines.values():
        e.warmup()
    params, _ = lm.snapshot_params()

    rng = np.random.default_rng(17)
    reqs = _spec_trace(rng, cfg.vocab_size, max_prompt=12, max_new=10)
    outs = {}
    for k in engines:
        futs = [srv.submit(f"lm_k{k}", {"prompt": p, "max_new": n})
                for p, n in reqs]
        outs[k] = [f.result(timeout=120)["result"] for f in futs]
    for i, (p, n) in enumerate(reqs):
        expect = _oracle(cfg, params, p, n)
        np.testing.assert_array_equal(
            outs[0][i], expect, err_msg=f"spec_k=0 diverged, req {i}")
        np.testing.assert_array_equal(
            outs[3][i], expect, err_msg=f"spec_k=3 diverged, req {i}")
    spec, base = engines[3].stats(), engines[0].stats()
    assert spec["spec_accepted"] > 0, "trace never speculated"
    assert spec["spec_steps"] > 0
    assert 0.0 < spec["acceptance_rate"] <= 1.0
    assert spec["accepted_per_step"] > 0.0
    # one-trace-under-speculation: drafts/acceptance are data, never
    # shapes — and the baseline engine never compiled a verify program
    assert spec["verify_traces"] == 1
    assert engines[0].verify_cache_size() == 0
    for e in engines.values():
        s = e.stats()
        assert s["step_traces"] == 1, s
        assert s["decode_step_retraces"] == 0
        assert e.prefill_cache_size() == 1
    if prefix:
        assert spec["prefix_hits"] > 0, \
            "trace never hit the prefix cache; test needs a new seed"
        assert spec["cow_copies"] >= 1          # the full-hit repeat
        assert _jit_cache_size(engines[3]._cow_fn) == 1
    assert spec["tokens"] == base["tokens"] == sum(n for _, n in reqs)
    engines[3]._pool.check()
    assert engines[3].pool_drift() is None


def test_spec_eos_inside_window_truncates(mv_session):
    """A drafted window that runs PAST eos must truncate exactly where
    sequential decode stops: emissions after the eos token are dropped,
    the slot turns over, and blocks return."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    params, _ = lm.snapshot_params()
    # repetitive probe => cyclic generation => speculative windows; the
    # eos must FIRST occur at continuation index >= 2 so truncation
    # lands inside/after a speculative window rather than on the
    # prefill's first token — scan seeds for a (probe, eos) pair whose
    # free-running oracle provides one (cycles repeat tokens fast, so
    # a fixed index could alias the first token)
    probe = eos = None
    for seed in range(29, 61):
        rng = np.random.default_rng(seed)
        motif = rng.integers(1, cfg.vocab_size, 3).astype(np.int32)
        cand = np.tile(motif, 4)[:10].astype(np.int32)
        run = [int(t) for t in _oracle(cfg, params, cand, 12)]
        fresh = [j for j in range(2, len(run)) if run[j] not in run[:j]]
        if fresh:
            probe, eos = cand, run[fresh[0]]
            break
    assert probe is not None, "no workable eos candidate; widen the scan"

    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", lm, slots=2, max_prompt=12,
                                  max_new=12, eos_id=eos, kv_block_size=4,
                                  prefill_token_budget=4, spec_k=4)
    engine.warmup()
    out = srv.submit("lm", probe).result(timeout=120)["result"]
    np.testing.assert_array_equal(out, _oracle(cfg, params, probe, 12, eos))
    assert out[-1] == eos and 3 <= len(out) < 12
    s = engine.stats()
    assert s["spec_steps"] >= 1, "no verify window ran before eos"
    # accounting credits only REALIZED drafts: matches past the
    # truncating eos were never emitted, so accepted can never exceed
    # the request's extra (non-first) tokens
    assert s["spec_accepted"] <= len(out) - 1
    assert s["active_slots"] == 0
    assert s["kv_blocks_live"] == 0
    engine._pool.check()


def test_spec_multi_token_metrics_and_iter_span(mv_session):
    """Multi-token metrics correctness: every emitted token lands in a
    histogram exactly once (first token TTFT, the rest ITL — the step
    interval divides across the window), DECODE_TOKENS counts accepted
    tokens, and ``decode.iter`` carries the ``accepted`` attr whose sum
    matches the engine's accounting."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm_m", lm, slots=2, max_prompt=12,
                                  max_new=10, kv_block_size=4,
                                  prefill_token_budget=4, spec_k=3)
    engine.warmup()
    rng = np.random.default_rng(5)
    motif = rng.integers(1, cfg.vocab_size, 3).astype(np.int32)
    prompts = [np.tile(motif, 4)[:10].astype(np.int32) for _ in range(4)]
    trace.enable(65536)
    try:
        futs = [srv.submit("lm_m", {"prompt": p, "max_new": 10})
                for p in prompts]
        outs = [f.result(timeout=120)["result"] for f in futs]
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and sum(s.name == "decode.iter"
                       for s in trace.collector().spans()) == 0):
            time.sleep(0.01)
        spans = trace.collector().spans()
    finally:
        trace.disable()
        trace.collector().clear()
    s = engine.stats()
    tokens = sum(len(o) for o in outs)
    assert s["tokens"] == tokens == 40
    assert Dashboard.get_or_create_counter("DECODE_TOKENS[lm_m]").get() \
        == tokens
    assert Dashboard.get_or_create_counter("SPEC_ACCEPTED[lm_m]").get() \
        == s["spec_accepted"] > 0
    # per-token histogram accounting: one TTFT per request, one ITL for
    # every other emitted token — speculation changes neither total
    assert engine.ttft_hist.count == len(prompts)
    assert engine.itl_hist.count == tokens - len(prompts)
    iters = [sp for sp in spans if sp.name == "decode.iter"]
    assert iters and all("accepted" in sp.attrs for sp in iters)
    # each request's accepted attrs sum to its extra (drafted) tokens
    assert sum(sp.attrs["accepted"] for sp in iters) \
        == s["spec_accepted"] > 0
    # the amortization itself: fused-step dispatches < decode tokens
    # they emitted (> 1 token per engine iteration on this trace)
    steps = Dashboard.get_or_create_counter("DECODE_STEPS[lm_m]").get()
    assert steps < tokens - len(prompts)


def test_queued_full_hit_window_itl_excludes_queue_wait(mv_session):
    """Regression (review finding): a fully-cached admission's first
    iteration can be a speculative window emitting several tokens; its
    ITL samples divide (now - t_last), and t_last used to still be the
    ENQUEUE time — a full hit that sat queued behind a long generation
    injected its whole queue wait into the ITL histogram. The base now
    moves to admission, so window ITL stays on the order of one step
    even when TTFT (which legitimately includes the wait) is huge."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    # pool sized past the occupant's 11-block reservation + the seeded
    # cached blocks, so pressure never evicts the victim's full hit
    engine = srv.register_decoder("lm_q", lm, slots=1, max_prompt=8,
                                  max_new=38, kv_block_size=4,
                                  kv_pool_blocks=16,
                                  prefill_token_budget=4, spec_k=4)
    engine.warmup()
    rng = np.random.default_rng(33)
    motif = rng.integers(1, cfg.vocab_size, 2).astype(np.int32)
    hot = np.tile(motif, 4).astype(np.int32)       # 8 = 2 blocks, aligned
    # seed the cache so the victim is a FULL hit, then slow every fused
    # step so the occupant manufactures a deterministic ~0.5s queue wait
    srv.submit("lm_q", {"prompt": hot, "max_new": 2}).result(timeout=120)

    def slowed(fn):
        def run(*a, **k):
            # 80 ms per dispatch: even at perfect acceptance the
            # occupant (38 tokens / <= 5 per window) holds the one slot
            # for >= 8 iterations ~ 640 ms of victim queue wait, while
            # any honest per-token ITL share stays ~(80 ms / window)
            time.sleep(0.08)
            return fn(*a, **k)
        return run

    engine._step_fn = slowed(engine._step_fn)
    engine._verify_fn = slowed(engine._verify_fn)
    engine.reset_stats()
    occupant = srv.submit("lm_q", {"prompt": rng.integers(
        1, cfg.vocab_size, 3).astype(np.int32), "max_new": 38})
    victim = srv.submit("lm_q", {"prompt": hot.copy(), "max_new": 8})
    occupant.result(timeout=120)
    victim.result(timeout=120)
    s = engine.stats()
    assert s["prefix_hits"] >= 2 and s["cow_copies"] >= 1  # full hit ran
    assert s["spec_accepted"] > 0, "victim window never speculated"
    # the victim's TTFT legitimately carries its queue wait...
    ttft = engine.ttft_hist.summary()
    assert ttft["max_ms"] > 500.0
    # ...but no ITL sample may: window shares are admission->step walls
    # (pre-fix, the victim's first window divided its whole queue wait
    # across <= 5 tokens — >= 130 ms per sample at this geometry)
    itl = engine.itl_hist.summary()
    assert itl["max_ms"] < 120.0, itl


def test_spec_k0_metrics_surface_identical_to_today(mv_session):
    """The spec_k=0 regression face: no spec stats keys, no SPEC_*
    dashboard instruments, flat decode.iter spans (no ``accepted``
    attr), per-token histogram accounting unchanged — today's numbers
    exactly."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm_p", lm, slots=2, max_prompt=12,
                                  max_new=8, kv_block_size=4,
                                  prefill_token_budget=4, spec_k=0)
    engine.warmup()
    rng = np.random.default_rng(9)
    motif = rng.integers(1, cfg.vocab_size, 3).astype(np.int32)
    prompts = [np.tile(motif, 4)[:10].astype(np.int32) for _ in range(3)]
    trace.enable(65536)
    try:
        futs = [srv.submit("lm_p", {"prompt": p, "max_new": 8})
                for p in prompts]
        for f in futs:
            assert len(f.result(timeout=120)["result"]) == 8
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and sum(sp.name == "decode.iter"
                       for sp in trace.collector().spans()) == 0):
            time.sleep(0.01)
        spans = trace.collector().spans()
    finally:
        trace.disable()
        trace.collector().clear()
    s = engine.stats()
    assert not any(k.startswith("spec") or k == "acceptance_rate"
                   or k == "accepted_per_step" or k == "verify_traces"
                   for k in s), sorted(s)
    snapshot = Dashboard.snapshot()
    assert not any(name.startswith("SPEC_") and "lm_p" in name
                   for name in snapshot), sorted(snapshot)
    iters = [sp for sp in spans if sp.name == "decode.iter"]
    assert iters and all("accepted" not in sp.attrs for sp in iters)
    assert engine.ttft_hist.count == len(prompts)
    assert engine.itl_hist.count == s["tokens"] - len(prompts)
    assert engine.verify_cache_size() == 0


def test_spec_flight_recorder_columns_and_timeline(mv_session, tmp_path):
    """FIELDS gained spec_proposed/spec_accepted: a spec engine's ring
    carries real counts that reconcile with stats, a spec_k=0 engine's
    carries -1 (no spec data), and engine_timeline renders the
    acceptance strip for the former while staying tolerant of
    pre-PR-11 records that lack the columns entirely."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from tools.engine_timeline import load_ring, render, timeline_report

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engines = {
        k: srv.register_decoder(f"lm_fr{k}", lm, slots=2, max_prompt=12,
                                max_new=8, kv_block_size=4,
                                prefill_token_budget=4, spec_k=k)
        for k in (3, 0)
    }
    rng = np.random.default_rng(13)
    motif = rng.integers(1, cfg.vocab_size, 3).astype(np.int32)
    prompt = np.tile(motif, 4)[:10].astype(np.int32)
    for k, e in engines.items():
        e.warmup()
        srv.submit(f"lm_fr{k}", prompt).result(timeout=120)
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and sum(r["decode_toks"] for r in e.recorder.records())
               < e.stats()["tokens"]):
            time.sleep(0.01)
    spec_recs = engines[3].recorder.records()
    assert engines[3].recorder.meta["spec_k"] == 3
    assert any(r["spec_proposed"] > 0 for r in spec_recs)
    assert sum(max(0, r["spec_accepted"]) for r in spec_recs) \
        == engines[3].stats()["spec_accepted"] > 0
    base_recs = engines[0].recorder.records()
    assert all(r["spec_proposed"] == r["spec_accepted"] == -1
               for r in base_recs)
    assert "spec_k" not in engines[0].recorder.meta

    # timeline: acceptance strip for the spec ring, absent for spec_k=0
    path = str(tmp_path / "spec_ring.jsonl")
    engines[3].recorder.export_jsonl(path)
    meta, records = load_ring(path)
    report = timeline_report(records, buckets=4)
    assert report["spec_enabled"]
    assert report["spec_accepted"] > 0
    assert 0.0 < report["acceptance_rate"] <= 1.0
    text = render(report, meta.get("name", ""))
    assert "acceptance" in text and "accept" in text
    off_report = timeline_report(engines[0].recorder.records(), buckets=4)
    assert not off_report["spec_enabled"]
    assert "acceptance" not in render(off_report)
    # pre-PR-11 tolerance: records WITHOUT the spec columns (old dumps)
    legacy = [{k: v for k, v in r.items() if not k.startswith("spec_")}
              for r in records]
    legacy_report = timeline_report(legacy, buckets=4)
    assert not legacy_report["spec_enabled"]
    assert legacy_report["acceptance_rate"] == 0.0


def test_spec_validation_fail_fasts(mv_session):
    """spec_k rejects negatives."""
    from multiverso_tpu.log import FatalError
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    lm = TransformerLM(_small_cfg())
    srv = InferenceServer("t")
    with pytest.raises(FatalError):
        srv.register_decoder("bad_neg", lm, kv_block_size=4, spec_k=-1)


def test_prompt_lookup_index_unit():
    """The drafter in isolation: proposals continue the most recent
    EARLIER occurrence of the tail n-gram, never self-match, respect
    the limit, and extend incrementally."""
    from multiverso_tpu.serving.decode_engine import _PromptLookup

    d = _PromptLookup()
    d.extend([1, 2, 3, 4])
    # tail (3, 4) never seen before -> nothing to propose
    assert d.propose(4) == []
    d.extend([1, 2, 9])
    d.extend([1, 2])
    # seq = 1,2,3,4,1,2,9,1,2: the most RECENT earlier (1, 2) was
    # followed by 9 — its continuation is the draft, limit-clipped
    assert d.propose(3) == [9, 1, 2]
    assert d.propose(1) == [9]
    d.extend([9, 1, 2])
    # the newest earlier occurrence keeps winning as the index extends
    assert d.propose(2) == [9, 1]
    assert d.propose(0) == []
    # a fresh index with fewer than n tokens proposes nothing
    d2 = _PromptLookup()
    d2.extend([7])
    assert d2.propose(4) == []
    # a TIGHT cycle (period 2 < limit) follows through its own
    # extension and still fills the window instead of stalling at the
    # match boundary
    d3 = _PromptLookup()
    d3.extend([5, 6, 5, 6, 5])
    assert d3.propose(4) == [6, 5, 6, 5]
    assert d3.propose(3) == [6, 5, 6]
