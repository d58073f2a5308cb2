"""Engine-loop phases on the profiler's clock (``trace.phase``).

The decode engine writes its loop's phases into a running
``jax.profiler`` session as host events named ``bench.engine.*``
(docs/OBSERVABILITY.md "Engine phases"); the benchmark's trace reduction
(``benchmarks/tracered.py``) books device-idle gaps to them and sums them
for the per-layer readers. Checked here, on the CPU:

* the names in the engine's source are the documented nine, and the
  prefix is the one the reduction admits;
* a toy engine under a real session leaves all nine, in the documented
  order (a pass's phases are children of ``engine.iter``, one after
  another: this pass's dispatches, then the syncs and the booking of
  what the pass BEFORE dispatched; a drain puts those first) and counted
  as the flight recorder counts, ``steps_ahead`` and ``drains`` as the
  trace shows them, and a pass that finds only block-starved waiters is
  a wait and no iteration;
* the reduction books a device-idle gap to the engine's phase and not to
  the longer harness span around it;
* each reader added with the phases computes its value from the summed
  spans and reads nothing where they are absent.

That a generation with no session builds no annotation is the guard
test's (``test_observability.py``).
"""

import glob
import os
import re
import time
import types

import numpy as np
import pytest

from benchmarks import harness, tracered
from multiverso_tpu import trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = trace.PROFILER_PREFIX


def _source_phases():
    with open(os.path.join(_REPO, "multiverso_tpu", "serving",
                           "decode_engine.py")) as fh:
        calls = re.findall(r"trace\.phase\(([^)]*)\)", fh.read())
    return sorted({name for call in calls
                   for name in re.findall(r'"(engine\.[a-z_.]+)"', call)})


def _documented_phases():
    """First column of the "Engine phases" table."""
    with open(os.path.join(_REPO, "docs", "OBSERVABILITY.md")) as fh:
        section = fh.read().split("## Engine phases")[1].split("\n## ")[0]
    return re.findall(r"^\| `(engine\.[a-z_.]+)` \|", section, re.M)


def test_prefix_is_the_one_the_trace_reduction_admits():
    assert trace.PROFILER_PREFIX == tracered.SPAN_PREFIX


@pytest.mark.parametrize("name", _source_phases())
def test_phase_name_is_documented(name):
    documented = _documented_phases()
    assert len(documented) == len(set(documented)) == 9
    assert name in documented
    assert len(_source_phases()) == 9


# -- a toy engine under a real profiler session ------------------------------

def _quiet(engine, hold_s=0.2, timeout_s=20.0):
    """The loop thread is back in its wait: no record for ``hold_s``."""
    deadline = time.monotonic() + timeout_s
    last, since = engine.recorder.total, time.monotonic()
    while time.monotonic() - since < hold_s:
        assert time.monotonic() < deadline, "the engine never went quiet"
        time.sleep(0.01)
        if engine.recorder.total != last:
            last, since = engine.recorder.total, time.monotonic()


def _inside(child, parents):
    return any(p[3] <= child[3] and child[3] + child[4] <= p[3] + p[4]
               for p in parents)


# the phases of one pass, in the order the loop enters them: what it
# dispatches, then what it retires (dispatched by the pass before)
_RETIRE = ("engine.step.sync", "engine.step.book",
           "engine.prefill_chunk.sync")
_PASS_ORDER = ("engine.step", "engine.admit", "engine.prefill_chunk") \
    + _RETIRE + ("engine.record",)


def test_toy_engine_leaves_all_nine_phases(mv_session, tmp_path):
    import jax

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=8,
                                  prefill_token_budget=4)
    prompt = np.arange(1, 7, dtype=np.int32)
    srv.submit("lm", prompt).result(timeout=120)      # compiles
    _quiet(engine)
    total0 = engine.recorder.total
    engine.reset_stats()
    assert trace.phase("engine.step") is trace.NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.phase("probe") is not trace.NULL_SPAN
        srv.submit("lm", prompt).result(timeout=120)
        _quiet(engine)          # a whole engine.wait inside the session
        futs = [srv.submit("lm", prompt[: 3 + i]) for i in range(3)]
        for f in futs:
            f.result(timeout=120)
        _quiet(engine)
    finally:
        jax.profiler.stop_trace()
    records = [r for r in engine.recorder.records() if r["it"] > total0]
    # a step was dispatched where the record has the blocks it read
    steps = sum(r["kv_live_block_share"] >= 0 for r in records)
    assert len(records) == engine.recorder.total - total0 and steps > 0
    stats = engine.stats()
    assert stats["steps"] == steps

    rows, spans = _engine_spans(tmp_path)
    assert sorted(spans) == sorted(P + n for n in _documented_phases())

    def n(name):
        return spans[P + name]["n"]

    def s(*names):
        return sum(spans[P + name]["s"] for name in names)

    assert n("engine.iter") == len(records)
    assert n("engine.record") == n("engine.admit") == len(records)
    assert n("engine.step") == n("engine.step.sync") \
        == n("engine.step.book") == steps
    chunks = sum(r["prefill_toks"] > 0 for r in records)
    assert n("engine.prefill_chunk") == chunks > 0
    # every chunk is waited for once, by its own logits
    assert n("engine.prefill_chunk.sync") == chunks
    by_name = {}
    for row in rows:
        by_name.setdefault(row[2][len(P):], []).append(row)
    # every phase of a pass is a child of engine.iter; the loop thread
    # is in a wait or in an iteration, never in both
    assert s(*_PASS_ORDER) <= s("engine.iter")
    for child in _PASS_ORDER:
        assert all(_inside(c, by_name["engine.iter"])
                   for c in by_name[child]), child
    assert not any(_inside(w, by_name["engine.iter"])
                   for w in by_name["engine.wait"])
    # inside a pass they follow one another in the documented order and
    # none overlaps another: so each .sync holds only a wait. A pass
    # dispatches first and retires afterwards: the step it syncs is the
    # one the pass before dispatched, so its own step went out AHEAD. A
    # drain puts the retiring phases first, and nothing is left to
    # retire behind that pass's dispatches
    behind = ahead = drains = 0
    for it in by_name["engine.iter"]:
        kids = sorted((r for name in _PASS_ORDER for r in by_name[name]
                       if _inside(r, [it])), key=lambda r: r[3])
        for a, b in zip(kids, kids[1:]):
            assert a[3] + a[4] <= b[3], (a[2], b[2])
        names = [r[2][len(P):] for r in kids]
        first = min(names.index(name) for name in _PASS_ORDER
                    if name not in _RETIRE and name in names)
        drained, rest = names[:first], names[first:]
        assert drained == [name for name in _RETIRE
                           for _ in range(drained.count(name))], names
        assert rest == [name for name in _PASS_ORDER
                        for _ in range(rest.count(name))], names
        drains += bool(drained)
        assert not (drained and set(rest) & set(_RETIRE)), names
        behind += {"engine.step", "engine.prefill_chunk"} <= set(rest)
        ahead += {"engine.step", "engine.step.sync"} <= set(rest)
    assert behind == sum(r["chunks_behind_step"] for r in records) > 0
    assert behind == stats["chunks_behind_step"]
    assert ahead == sum(r["steps_ahead"] for r in records) > 0
    assert ahead == stats["steps_ahead"] < steps
    # a prompt's last chunk with nothing live beside it is retired
    # before anything else is dispatched or admitted
    assert drains == sum(stats["drains"].values()) > 0
    assert set(stats["drains"]) == {"empty"}


def _engine_spans(trace_dir):
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    rows = [r for r in tracered.load_events(paths[0])
            if r[2].startswith(P + "engine.")]
    return rows, tracered.reduce_events(rows, 1.0)["spans"]


def test_block_starved_pass_is_a_wait_and_no_iteration(mv_session,
                                                       tmp_path):
    """With the whole pool held, a queued request is not admissible and
    the loop spins every 0.5 ms: each spin is an ``engine.wait``, and
    ``engine.iter`` stays the count of the flight recorder's records."""
    import jax

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=8, kv_block_size=4,
                                  kv_pool_blocks=6, prefill_token_budget=4)
    prompt = np.arange(1, 7, dtype=np.int32)
    srv.submit("lm", prompt).result(timeout=120)      # compiles
    _quiet(engine)
    total0 = engine.recorder.total
    assert engine.squeeze_pool(1.0) == engine._pool.capacity
    jax.profiler.start_trace(str(tmp_path))
    try:
        fut = srv.submit("lm", prompt)
        deadline = time.monotonic() + 20.0
        while not len(engine._q):           # the front hands it over
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.2)                     # some hundred empty passes
        assert engine.recorder.total == total0 and not fut.done()
        engine.unsqueeze_pool()
        fut.result(timeout=120)
        _quiet(engine)
    finally:
        jax.profiler.stop_trace()
    worked = engine.recorder.total - total0
    _, spans = _engine_spans(tmp_path)
    assert spans[P + "engine.iter"]["n"] == worked > 0
    assert spans[P + "engine.record"]["n"] == worked
    assert spans[P + "engine.admit"]["n"] == worked
    assert spans[P + "engine.wait"]["n"] >= 5


# -- the reduction books an idle gap to the engine's phase --------------------

@pytest.mark.parametrize("wait_ms,first_token_goes_to", [
    # one long wait on the harness's thread: every gap goes to a phase
    (100, "engine.iter"),
    # the rule's limit, as read on the v5e (PERF.md section 5): the
    # serving driver waits in slices of 50 ms, shorter than engine.iter,
    # and a gap goes to the SHORTEST span over its middle
    (50, "wait_reply"),
])
def test_idle_gaps_go_to_the_engine_phase_not_the_harness_wait(
        wait_ms, first_token_goes_to):
    ms = 1e6
    dev, host = "/device:TPU:0", "/host:CPU"
    rows = [[host, "main", P + "window", 0.0, 100 * ms]]
    # the harness thread waits for a reply through all of it
    rows += [[host, "main", P + "wait_reply", at * ms, wait_ms * ms]
             for at in range(0, 100, wait_ms)]
    rows += [
        # device: a step 10..30, the chunk queued behind it 34..70; idle
        # 0..10, 30..34, 70..100
        [dev, "XLA Ops", "%fusion.1 = f32[8] fusion()", 10 * ms, 20 * ms],
        [dev, "XLA Ops", "%fusion.2 = f32[8] fusion()", 34 * ms, 36 * ms],
        # the engine's loop thread, one pass in the loop's order
        [host, "loop", P + "engine.iter", 2 * ms, 96 * ms],
        [host, "loop", P + "engine.step", 2 * ms, 7 * ms],
        [host, "loop", P + "engine.admit", 9 * ms, 1 * ms],
        [host, "loop", P + "engine.prefill_chunk", 10 * ms, 2 * ms],
        [host, "loop", P + "engine.step.sync", 12 * ms, 18 * ms],
        [host, "loop", P + "engine.step.book", 30 * ms, 6 * ms],
        [host, "loop", P + "engine.prefill_chunk.sync", 36 * ms, 34 * ms],
        [host, "loop", P + "engine.record", 96 * ms, 2 * ms],
    ]
    r = tracered.reduce_events(rows, 0.0)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 0..10 has its middle in the step's launch; 30..34 in the booking;
    # 70..100 after the chunk's sync, in the first token's booking,
    # which is the iteration's own time
    assert gaps == pytest.approx({P + "engine.step": 0.010,
                                  P + "engine.step.book": 0.004,
                                  P + first_token_goes_to: 0.030})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


# -- the readers added with the phases ---------------------------------------

def _ctx(spans=None, **tracered_keys):
    reduced = dict(tracered_keys)
    if spans is not None:
        reduced["spans"] = {P + k: {"s": s, "n": n}
                            for k, (s, n) in spans.items()}
    return types.SimpleNamespace(tracered=reduced, counters={}, chips=4)


_SPANS = {"engine.iter": (0.98, 10), "engine.step": (0.60, 10),
          "engine.step.sync": (0.55, 10), "engine.prefill_chunk": (0.24, 8),
          "engine.prefill_chunk.sync": (0.20, 12)}


_DP_OPS = {
    "fusion.218 bf16[1500000,300] fusion(bf16[1500000,300], s32[65536], "
    "bf16[65536,300])": 5.9,
    "all-reduce.14 (bf16[1024,5,300], bf16[65536,300]) all-reduce("
    "bf16[1024,5,300], bf16[65536,300])": 2.4,
    "psum_invariant.24 bf16[1500000,300] all-reduce(bf16[1500000,300])":
        0.8,
    "all-gather-start.2 (f32[8], f32[16]) all-gather-start(f32[8])": 0.1,
    "ag_done (f32[16]) all-gather-done((f32[8], f32[16]))": 0.3,
    "all_gather_fusion f32[8] fusion(f32[8])": 0.9,
}


@pytest.mark.parametrize("reader,ctx,want", [
    ("engine_decode_step_ms", _ctx(_SPANS), 60.0),
    ("engine_prefill_chunk_ms", _ctx(_SPANS), 30.0),
    # (0.98 - 0.55 - 0.20) s over 10 iterations
    ("engine_host_ms_per_iter", _ctx(_SPANS), 23.0),
    # summed over four chips: the all-reduce by name, the table psum by
    # opcode alone, an async pair; the scatter and the gather are none
    ("w2v_dp_collective_pct", _ctx(ops=_DP_OPS, chips=4, window_s=6.0),
     100 * (2.4 + 0.8 + 0.1 + 0.3) / (4 * 6.0)),
])
def test_reader_value_by_hand_and_nothing_without_its_spans(reader, ctx,
                                                            want):
    read = harness.load_module("readers", reader).read
    assert read(ctx) == pytest.approx(want)
    # the parent commit's trace: harness spans only, no collectives
    bare = _ctx({"wait_reply": (7.9, 150)}, chips=1, window_s=8.0,
                ops={"fusion.1 f32[8] fusion(f32[8])": 7.0})
    assert read(bare) is None
    assert read(types.SimpleNamespace(tracered=None, counters={},
                                      chips=1)) is None
