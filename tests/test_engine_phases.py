"""Engine-loop phases: one site, two clocks (``trace.PhaseClock``).

The decode engine's loop enters its phases through one clock object. On
the host's clock they are always on: ``eng.stats()`` (``phase_ms``,
``loop_gap_ms``, ``wait_ms_max``, ``slowest_pass``) and the flight
recorder's ``phases`` / ``gap_ms`` columns. Under a ``jax.profiler``
session the same sites also write host events named ``bench.engine.*``
(docs/OBSERVABILITY.md "Engine phases"); the benchmark's trace reduction
(``benchmarks/tracered.py``) books device-idle gaps to them and sums them
for the per-layer readers. Checked here, on the CPU:

* the names in the engine's source are the documented ten, and the
  prefix is the one the reduction admits;
* a toy engine under a real session leaves all ten, in the documented
  order (a pass's phases are children of ``engine.iter``, one after
  another: this pass's dispatches, then the syncs and the bookings of
  what the pass BEFORE dispatched; a drain puts those first) and counted
  as the flight recorder and the always-on clock count, ``steps_ahead``
  and ``drains`` as the trace shows them, and a pass that finds only
  block-starved waiters is a wait and no iteration;
* with NO session the clock tiles the pass; a stall planted in a phase
  is named by ``slowest_pass`` after the ring has wrapped, one planted
  between two passes is a loop gap and no phase; ``reset_stats()``
  starts all of it again; the queue wait is a histogram of its own;
* a dump from before the two columns still loads and renders;
* the reduction books a device-idle gap to the engine's phase and not to
  the longer harness span around it;
* each reader added with the phases computes its value from the summed
  spans and reads nothing where they are absent.

That a generation with no session builds no annotation and no object a
pass is the guard test's (``test_observability.py``).
"""

import glob
import json
import os
import re
import threading
import time
import types

import numpy as np
import pytest

from benchmarks import harness, tracered
from multiverso_tpu import config, trace
from multiverso_tpu.serving import decode_engine, flight_recorder
from tools import engine_timeline

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = trace.PROFILER_PREFIX


def _source_phases():
    with open(os.path.join(_REPO, "multiverso_tpu", "serving",
                           "decode_engine.py")) as fh:
        calls = re.findall(r"self\._phase\(([^)]*)\)", fh.read())
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
    assert len(documented) == len(set(documented)) == 10
    assert name in documented
    assert len(_source_phases()) == 10
    # the clock's own list: a pass's leaves, the pass and the wait
    assert sorted(_source_phases()) == sorted(
        decode_engine._PASS_PHASES + ("engine.iter", "engine.wait"))


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
           "engine.prefill_chunk.sync", "engine.prefill_chunk.book")
_PASS_ORDER = ("engine.step", "engine.admit", "engine.prefill_chunk") \
    + _RETIRE + ("engine.record",)


def test_pass_order_is_the_clocks():
    assert _PASS_ORDER == decode_engine._PASS_PHASES[:3] + _RETIRE \
        + ("engine.record",)
    assert sorted(_PASS_ORDER) == sorted(decode_engine._PASS_PHASES)


def _toy_engine(**engine_kw):
    """A warm toy engine, back in its wait, and its server."""
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=8, max_new=8, prefill_token_budget=4)
    kw.update(engine_kw)
    engine = srv.register_decoder("lm", TransformerLM(cfg), **kw)
    srv.submit("lm", _PROMPT).result(timeout=120)      # compiles
    _quiet(engine)
    return srv, engine


_PROMPT = np.arange(1, 7, dtype=np.int32)


def test_toy_engine_leaves_all_ten_phases(mv_session, tmp_path):
    import jax

    srv, engine = _toy_engine()
    prompt = _PROMPT
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
    assert n("engine.prefill_chunk.book") == chunks
    # one site, two clocks: the always-on clock counted the same passes
    # and phases (the wait it was in when the session began is its own
    # alone)
    clocked = stats["phase_ms"]
    assert sorted(clocked) == sorted(_documented_phases())
    for name in _PASS_ORDER + ("engine.iter",):
        assert clocked[name]["n"] == n(name), name
    assert clocked["engine.wait"]["n"] == n("engine.wait") + 1
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

    srv, engine = _toy_engine(kv_block_size=4, kv_pool_blocks=6)
    prompt = _PROMPT
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


# -- the always-on clock: no session ------------------------------------------

_LEAVES = decode_engine._PASS_PHASES


def _generate(srv, n=3, **payload):
    futs = [srv.submit("lm", {"prompt": _PROMPT[: 3 + i % 4], **payload})
            for i in range(n)]
    return [f.result(timeout=120) for f in futs]


def test_no_session_clock_tiles_the_pass(mv_session):
    """With no profiler session ``stats()`` holds all ten phases; a
    pass's leaves and its time under no leaf are the pass, which to the
    start of its record is the recorder's ``busy_ms``; ``step_ms`` is
    the three step phases of the row."""
    srv, engine = _toy_engine()
    total0 = engine.recorder.total
    engine.reset_stats()
    _generate(srv, 6)
    _quiet(engine)
    stats = engine.stats()
    clocked = stats["phase_ms"]
    assert sorted(clocked) == sorted(_documented_phases())
    assert all(v["n"] > 0 and v["ms"] > 0 for v in clocked.values())
    records = [r for r in engine.recorder.records() if r["it"] > total0]
    assert clocked["engine.iter"]["n"] == len(records)
    leaves = sum(clocked[name]["ms"] for name in _LEAVES)
    unphased = clocked["engine.iter"]["ms"] - leaves
    assert 0 < unphased < 0.25 * clocked["engine.iter"]["ms"]
    busy = sum(r["busy_ms"] for r in records)
    assert leaves - clocked["engine.record"]["ms"] + unphased \
        == pytest.approx(busy, rel=0.05)
    # the ring's row: the leaves in the meta line's order, under the pass
    assert engine.recorder.meta["phases"] == list(_LEAVES)
    for r in records:
        assert len(r["phases"]) == len(_LEAVES)
        # busy_ms ends where the record begins, its leaves lie inside
        assert sum(r["phases"][:-1]) <= r["busy_ms"] + 1e-6
        assert r["step_ms"] == pytest.approx(
            r["phases"][0] + r["phases"][3] + r["phases"][4])
        assert r["gap_ms"] >= 0
    for i, name in enumerate(_LEAVES[:-1]):
        assert sum(r["phases"][i] for r in records) \
            == pytest.approx(clocked[name]["ms"])
    gap = stats["loop_gap_ms"]
    assert gap["n"] == len(records) and 0 < gap["ms"] and 0 < gap["max"]
    assert gap["ms"] == pytest.approx(sum(r["gap_ms"] for r in records))
    assert stats["wait_ms_max"] > 0
    slow = stats["slowest_pass"]
    assert sorted(slow) == ["busy_ms", "gap_before_ms", "it", "live",
                            "phases", "queue", "ts"]
    assert list(slow["phases"]) == list(_LEAVES)
    assert slow["busy_ms"] >= max(r["busy_ms"] for r in records)
    assert total0 < slow["it"] <= engine.recorder.total
    json.dumps(stats)           # the watchdog bundle's stats.json


def test_stall_in_a_phase_is_named_after_the_ring_wrapped(mv_session,
                                                          monkeypatch):
    capacity = config.get_flag("flight_recorder_capacity")
    config.set_flag("flight_recorder_capacity", 8)
    try:
        srv, engine = _toy_engine()
    finally:
        config.set_flag("flight_recorder_capacity", capacity)
    assert engine.recorder.capacity == 8
    engine.reset_stats()
    real, planted = engine._book_step, []

    def slow_book(step, nxt):
        if not planted:
            planted.append(engine.iters_total + 1)
            time.sleep(0.2)
        return real(step, nxt)

    monkeypatch.setattr(engine, "_book_step", slow_book)
    _generate(srv, 4)
    _quiet(engine)
    assert engine.recorder.total - planted[0] > 8       # wrapped past it
    assert planted[0] not in [r["it"] for r in engine.recorder.records()]
    slow = engine.stats()["slowest_pass"]
    assert slow["it"] == planted[0] and slow["busy_ms"] >= 200
    assert max(slow["phases"], key=slow["phases"].get) == "engine.step.book"
    assert slow["phases"]["engine.step.book"] >= 200
    assert slow["gap_before_ms"] < 100


class _SlowFlag:
    """``engine._stop`` with a sleep planted in the loop thread's next
    look at it: between two passes, under no phase."""

    def __init__(self, engine):
        self._event, self._thread = engine._stop, engine._thread
        self.sleep_s = 0.0

    def is_set(self):
        if self.sleep_s and threading.current_thread() is self._thread:
            pause, self.sleep_s = self.sleep_s, 0.0
            time.sleep(pause)
        return self._event.is_set()

    def set(self):
        self._event.set()


def test_stall_between_passes_is_a_loop_gap_and_no_phase(mv_session):
    srv, engine = _toy_engine()
    engine._stop = flag = _SlowFlag(engine)
    total0 = engine.recorder.total
    engine.reset_stats()
    flag.sleep_s = 0.2
    _generate(srv, 2)
    _quiet(engine)
    assert not flag.sleep_s
    stats = engine.stats()
    records = [r for r in engine.recorder.records() if r["it"] > total0]
    gapped = [r for r in records if r["gap_ms"] >= 200]
    assert len(gapped) == 1
    assert stats["loop_gap_ms"]["max"] == pytest.approx(gapped[0]["gap_ms"])
    # in no phase and in no pass
    assert max(r["busy_ms"] for r in records) < 150
    assert stats["slowest_pass"]["busy_ms"] < 150
    assert sum(stats["phase_ms"][name]["ms"]
               for name in _LEAVES + ("engine.iter",)) < 2 * 150
    if stats["slowest_pass"]["it"] == gapped[0]["it"]:
        assert stats["slowest_pass"]["gap_before_ms"] >= 200


def test_reset_stats_starts_the_clock_again(mv_session):
    srv, engine = _toy_engine()
    _generate(srv, 2)
    _quiet(engine)
    assert engine.stats()["slowest_pass"] is not None
    assert engine.qwait_hist.summary()["count"] > 0
    engine.reset_stats()
    stats = engine.stats()
    assert all(stats["phase_ms"][name] == {"ms": 0.0, "n": 0}
               for name in _LEAVES + ("engine.iter",))
    assert stats["loop_gap_ms"] == {"ms": 0.0, "n": 0, "max": 0.0}
    assert stats["wait_ms_max"] == 0.0 and stats["slowest_pass"] is None
    assert stats["queue_wait_p50_ms"] == stats["queue_wait_p99_ms"] == 0.0
    assert engine.qwait_hist.summary()["count"] == 0
    _generate(srv, 1)
    _quiet(engine)
    assert engine.stats()["phase_ms"]["engine.iter"]["n"] > 0


def test_queue_wait_is_a_histogram_of_its_own(mv_session):
    """One ``SERVE_QWAIT`` sample a request admitted; TTFT is that wait
    plus admission-to-first-token, so the wait's median lies under it."""
    from multiverso_tpu.dashboard import Dashboard

    srv, engine = _toy_engine()
    engine.reset_stats()
    n = 8                       # 2 slots: most of them queue
    _generate(srv, n)
    _quiet(engine)
    stats = engine.stats()
    hist = Dashboard.get_or_create_histogram("SERVE_QWAIT[lm]")
    assert hist is engine.qwait_hist
    assert hist.summary()["count"] == n == engine.ttft_hist.summary()["count"]
    assert 0 < stats["queue_wait_p50_ms"] <= stats["ttft_p50_ms"]
    assert stats["queue_wait_p50_ms"] <= stats["queue_wait_p99_ms"] \
        <= stats["ttft_p99_ms"]


def test_dump_from_before_the_columns_loads_and_renders(mv_session,
                                                        tmp_path, capsys):
    """``FIELDS`` grew at the end only: a dump whose records stop at
    ``steps_ahead`` zips against it and renders without the phase lines;
    a new one renders the per-phase totals and the slowest passes."""
    assert flight_recorder.FIELDS[-2:] == ("phases", "gap_ms")
    assert flight_recorder.FIELDS[25] == "steps_ahead"
    srv, engine = _toy_engine()
    _generate(srv, 3)
    _quiet(engine)
    new = str(tmp_path / "new.jsonl")
    assert engine.recorder.export_jsonl(new) > 0
    meta, records = engine_timeline.load_ring(new)
    assert meta["phases"] == list(_LEAVES)
    report = engine_timeline.timeline_report(records, phases=meta["phases"])
    assert list(report["phase_ms"]) == list(_LEAVES) + ["unphased",
                                                        "loop_gap"]
    assert sum(report["phase_ms"][k] for k in _LEAVES) \
        + report["phase_ms"]["unphased"] \
        == pytest.approx(sum(r["busy_ms"] for r in records))
    assert [s["busy_ms"] for s in report["slowest"]] == sorted(
        (r["busy_ms"] for r in records), reverse=True)[:3]
    assert engine_timeline.main([new]) == 0
    text = capsys.readouterr().out
    assert "phases (ms a pass)" in text and "slowest passes" in text
    assert "step.book" in text
    # the same dump as PR 35 would have written it
    old = str(tmp_path / "old.jsonl")
    old_fields = list(flight_recorder.FIELDS[:26])
    with open(old, "w") as fh:
        del meta["phases"]
        meta["fields"] = old_fields
        fh.write(json.dumps({"flight_recorder": meta}) + "\n")
        for r in records:
            fh.write(json.dumps({k: r[k] for k in old_fields}) + "\n")
    ring = flight_recorder.FlightRecorder(capacity=8)
    ring.record(tuple(records[0][k] for k in old_fields))
    assert "phases" not in ring.records()[0]
    assert ring.summary()["iterations"] == 1
    assert engine_timeline.main([old]) == 0
    text = capsys.readouterr().out
    assert "utilization" in text and "phases (ms a pass)" not in text


# -- the reduction books an idle gap to the engine's phase --------------------

@pytest.mark.parametrize("wait_ms,booked,first_token_goes_to", [
    # one long wait on the harness's thread: every gap goes to a phase
    (100, False, "engine.iter"),
    # the rule's limit, as read on the v5e (PERF.md section 5): the
    # serving driver waits in slices of 50 ms, shorter than engine.iter,
    # and a gap goes to the SHORTEST span over its middle
    (50, False, "wait_reply"),
    # with the chunk's booking a phase of its own
    (100, True, "engine.prefill_chunk.book"),
    (50, True, "engine.prefill_chunk.book"),
])
def test_idle_gaps_go_to_the_engine_phase_not_the_harness_wait(
        wait_ms, booked, first_token_goes_to):
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
    ] + ([[host, "loop", P + "engine.prefill_chunk.book", 70 * ms, 26 * ms]]
         if booked else [])
    r = tracered.reduce_events(rows, 0.0)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 0..10 has its middle in the step's launch; 30..34 in the booking;
    # 70..100 after the chunk's sync, in the first token's booking:
    # until PR 36 the iteration's own time, since then a phase (shorter
    # than the harness's slice, so it wins)
    assert gaps == pytest.approx({P + "engine.step": 0.010,
                                  P + "engine.step.book": 0.004,
                                  P + first_token_goes_to: 0.030})
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


# -- the readers added with the phases ---------------------------------------

def _ctx(spans=None, counters=None, **tracered_keys):
    reduced = dict(tracered_keys)
    if spans is not None:
        reduced["spans"] = {P + k: {"s": s, "n": n}
                            for k, (s, n) in spans.items()}
    return types.SimpleNamespace(tracered=reduced, counters=counters or {},
                                 chips=4)


_SPANS = {"engine.iter": (0.98, 10), "engine.step": (0.60, 10),
          "engine.step.sync": (0.55, 10), "engine.prefill_chunk": (0.24, 8),
          "engine.prefill_chunk.sync": (0.20, 12)}
# a pass tiled by its eight leaves, a wait beside it, in a 1.2 s window
_TILED = {"engine.iter": (1.00, 10), "engine.wait": (0.10, 2),
          "engine.step": (0.20, 10), "engine.admit": (0.05, 10),
          "engine.prefill_chunk": (0.10, 8), "engine.step.sync": (0.30, 10),
          "engine.step.book": (0.15, 10),
          "engine.prefill_chunk.sync": (0.08, 8),
          "engine.prefill_chunk.book": (0.06, 8), "engine.record": (0.02, 10)}
_UNBOOKED = {k: v for k, v in _TILED.items()
             if k != "engine.prefill_chunk.book"}


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
    # 0.06 s over 8 chunks
    ("engine_chunk_book_ms", _ctx(_TILED), 7.5),
    # (1.2 - 0.10 - 0.96) s over 10 iterations: 4 ms inside the passes
    # and 10 ms of loop gap between them
    ("engine_unphased_ms_per_iter", _ctx(_TILED, window_s=1.2), 14.0),
    # the parent's trace: the chunk's booking is under no phase yet
    ("engine_unphased_ms_per_iter", _ctx(_UNBOOKED, window_s=1.2), 20.0),
    ("engine_program_retraces",
     _ctx(counters={"engine": {"step_traces": 2, "prefill_traces": 3}}),
     3.0),
])
def test_reader_value_by_hand_and_nothing_without_its_spans(reader, ctx,
                                                            want):
    read = harness.load_module("readers", reader).read
    assert read(ctx) == pytest.approx(want)
    assert isinstance(read(ctx), float)
    # the parent commit's trace: harness spans only, no collectives
    bare = _ctx({"wait_reply": (7.9, 150)}, chips=1, window_s=8.0,
                ops={"fusion.1 f32[8] fusion(f32[8])": 7.0})
    assert read(bare) is None
    assert read(types.SimpleNamespace(tracered=None, counters={},
                                      chips=1)) is None


def test_program_retraces_reports_a_true_zero():
    read = harness.load_module("readers", "engine_program_retraces").read
    warm = {"engine": {"step_traces": 1, "prefill_traces": 1}}
    assert read(_ctx(counters=warm)) == 0.0
    assert read(_ctx(counters={"engine": {"ttft_p50_ms": 3.0}})) is None
