"""Watchdog (serving/watchdog.py): triggers, bundles, and the e2e stall.

The trigger matrix runs against a duck-typed fake engine (fast, exact);
the end-to-end test wedges a REAL engine's fused step and requires the
live watchdog thread to trip within its deadline, dump the full bundle
(flight ring + stats + dashboard + thread stacks), and count the trip.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.dashboard import Dashboard
from multiverso_tpu.serving.watchdog import (EngineWatchdog, WatchdogConfig,
                                             thread_stacks)


class _FakeEngine:
    """The watchdog's whole contract: health() / pool_drift() / stats()
    / name / recorder."""

    name = "fake"

    def __init__(self):
        self.h = {"iters_total": 7, "last_iter_age_s": 0.0, "live_seqs": 0,
                  "active_slots": 0, "queue_depth": 0, "queue_age_s": 0.0,
                  "stopped": False}
        self.drift = None
        self.recorder = None

    def health(self):
        return dict(self.h)

    def pool_drift(self):
        return self.drift

    def stats(self):
        return {"marker": 123, **self.h}


@pytest.fixture()
def fake_wd(tmp_path):
    Dashboard.reset()
    engine = _FakeEngine()
    wd = EngineWatchdog(engine, WatchdogConfig(
        stall_s=0.5, queue_age_s=2.0, dump_dir=str(tmp_path)), start=False)
    yield engine, wd
    Dashboard.reset()


def test_stall_requires_live_work_and_rearms(fake_wd):
    engine, wd = fake_wd
    assert wd.check_once() == []                  # healthy
    engine.h["last_iter_age_s"] = 5.0
    assert wd.check_once() == []                  # idle != stalled
    engine.h["live_seqs"] = 2
    fired = wd.check_once()
    assert len(fired) == 1 and "stall" in fired[0]
    assert wd.check_once() == []                  # edge-triggered
    engine.h["last_iter_age_s"] = 0.0             # progress resumed
    assert wd.check_once() == []
    engine.h["last_iter_age_s"] = 5.0             # stalls AGAIN: re-armed
    assert len(wd.check_once()) == 1
    assert wd.trip_count == 2
    assert Dashboard.get_or_create_counter(
        "WATCHDOG_TRIPS[fake]").get() == 2


def test_queue_age_breach_trips(fake_wd):
    engine, wd = fake_wd
    engine.h["queue_age_s"] = 1.0
    assert wd.check_once() == []                  # under the limit
    engine.h["queue_age_s"] = 3.0
    fired = wd.check_once()
    assert len(fired) == 1 and "queue-age breach" in fired[0]
    assert wd.trips[0][0] == "queue_age"


def test_pool_drift_needs_two_consecutive_verdicts(fake_wd):
    engine, wd = fake_wd
    engine.drift = "leak: 2 free + 1 live != capacity 4"
    assert wd.check_once() == []                  # first sighting arms
    fired = wd.check_once()                       # verdict persisted
    assert len(fired) == 1 and "block-pool drift" in fired[0]
    # a transient that CLEARS between polls never trips
    wd2 = EngineWatchdog(engine, wd.config, start=False)
    engine.drift = "leak: transient"
    assert wd2.check_once() == []
    engine.drift = None
    assert wd2.check_once() == []
    assert wd2.trip_count == 0
    # the VERDICT must persist, not the exact message: a real leak's
    # free/live counts fluctuate under live traffic poll to poll
    wd3 = EngineWatchdog(engine, wd.config, start=False)
    engine.drift = "leak: 2 free + 1 live != capacity 4"
    assert wd3.check_once() == []
    engine.drift = "leak: 1 free + 2 live != capacity 4"
    fired = wd3.check_once()
    assert len(fired) == 1 and "block-pool drift" in fired[0]


def test_stopped_engine_never_trips(fake_wd):
    engine, wd = fake_wd
    engine.h.update(stopped=True, live_seqs=3, last_iter_age_s=99.0,
                    queue_age_s=99.0)
    engine.drift = "leak"
    assert wd.check_once() == []
    assert wd.check_once() == []
    assert wd.trip_count == 0


def test_bundle_layout_and_no_dump_dir(fake_wd, tmp_path):
    engine, wd = fake_wd
    engine.h.update(live_seqs=1, last_iter_age_s=5.0)
    wd.check_once()
    kind, reason, bundle = wd.trips[0]
    assert kind == "stall" and bundle is not None
    files = set(os.listdir(bundle))
    assert {"stats.json", "dashboard.json", "stacks.txt"} <= files
    meta = json.load(open(os.path.join(bundle, "stats.json")))
    assert meta["kind"] == "stall" and meta["engine"] == "fake"
    assert meta["stats"]["marker"] == 123
    json.load(open(os.path.join(bundle, "dashboard.json")))   # valid JSON
    assert "MainThread" in open(os.path.join(bundle, "stacks.txt")).read()
    # without a dump dir the trip still counts, with no bundle
    engine2 = _FakeEngine()
    engine2.h.update(live_seqs=1, last_iter_age_s=5.0)
    seen = []
    wd2 = EngineWatchdog(engine2, WatchdogConfig(
        stall_s=0.5, on_trip=lambda r, b: seen.append((r, b))),
        start=False)
    wd2.check_once()
    assert wd2.trips[0][2] is None
    assert seen and seen[0][1] is None and "stall" in seen[0][0]


def test_flapping_condition_bounded_memory_and_bundles(fake_wd):
    """A condition oscillating around its threshold re-trips every
    clear/re-breach cycle; trips must stay counted but bounded in memory
    and STOP writing bundles at max_bundles (each bundle is a full
    ring + snapshot + stacks — unbounded dumps fill the degraded
    replica's own disk)."""
    engine, wd = fake_wd
    for _ in range(70):
        engine.h["queue_age_s"] = 3.0             # breach
        assert len(wd.check_once()) == 1
        engine.h["queue_age_s"] = 0.0             # clear -> re-arm
        assert wd.check_once() == []
    assert wd.trip_count == 70
    assert Dashboard.get_or_create_counter(
        "WATCHDOG_TRIPS[fake]").get() == 70
    assert len(wd.trips) == 64                    # bounded, newest kept
    assert wd.bundles == wd.config.max_bundles == 16
    # bundles stopped at trip 16: everything after is count-and-log only
    assert all(t[2] is None for t in list(wd.trips)[-54:])
    assert sum(os.path.isdir(os.path.join(wd.config.dump_dir, d))
               for d in os.listdir(wd.config.dump_dir)) == 16


def test_thread_stacks_cover_live_threads():
    text = thread_stacks()
    assert "MainThread" in text
    assert "test_thread_stacks_cover_live_threads" in text


# -- real engine --------------------------------------------------------------

def test_injected_stall_trips_within_deadline_e2e(mv_session, tmp_path):
    """The acceptance walk: a wedged fused step on a live engine trips
    the RUNNING watchdog thread within stall_s + ~2 polls, the bundle
    holds the iteration ring and the wedged thread's stack, and
    WATCHDOG_TRIPS increments — then the engine recovers and finishes
    the generation once unblocked."""
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=8, watchdog=False)
    # a healthy generation first, so the flight ring holds real records
    out = srv.submit("lm", np.arange(1, 5, dtype=np.int32)).result(
        timeout=60)
    assert len(out["result"]) == 8

    tripped = threading.Event()
    engine.watchdog = EngineWatchdog(engine, WatchdogConfig(
        interval_s=0.05, stall_s=0.4, queue_age_s=0.0,
        dump_dir=str(tmp_path),
        on_trip=lambda reason, bundle: tripped.set()))

    release = threading.Event()
    orig_step = engine._step_fn

    def wedged_step(*args, **kwargs):
        release.wait(30)
        return orig_step(*args, **kwargs)

    engine._step_fn = wedged_step
    t0 = time.monotonic()
    fut = srv.submit("lm", np.arange(1, 6, dtype=np.int32))
    try:
        assert tripped.wait(5.0), "watchdog missed its deadline"
        trip_latency = time.monotonic() - t0
        assert trip_latency < 5.0
        wd = engine.watchdog
        assert wd.trip_count == 1
        kind, reason, bundle = wd.trips[0]
        assert kind == "stall" and "live sequence" in reason
        files = set(os.listdir(bundle))
        assert {"stats.json", "dashboard.json", "stacks.txt",
                "ring.jsonl"} <= files
        # the ring dump: meta line + the healthy generation's iterations
        lines = open(os.path.join(bundle, "ring.jsonl")).read().splitlines()
        assert json.loads(lines[0])["flight_recorder"]["name"] == "lm"
        assert len(lines) - 1 >= 5                # >= max_new-1 iterations
        # the stack dump shows WHERE the engine thread is wedged
        stacks = open(os.path.join(bundle, "stacks.txt")).read()
        assert "serve-decode-lm" in stacks and "wedged_step" in stacks
        snap = Dashboard.snapshot()
        assert snap["WATCHDOG_TRIPS[lm]"]["value"] == 1
        assert engine.stats()["watchdog_trips"] == 1
    finally:
        release.set()
    # unwedged: the generation completes and the stall re-arms
    assert len(fut.result(timeout=60)["result"]) == 8


def test_pool_drift_detector_on_real_engine(mv_session):
    """A hand-corrupted block pool (blocks allocated behind the engine's
    back) fires the drift detector after the two-poll persistence; a
    healthy engine never does."""
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=4, watchdog=False)
    wd = EngineWatchdog(engine, WatchdogConfig(stall_s=30.0), start=False)
    out = srv.submit("lm", np.arange(1, 5, dtype=np.int32)).result(
        timeout=60)
    assert len(out["result"]) == 4
    for _ in range(4):                            # healthy: forever silent
        assert wd.check_once() == []
    assert engine.pool_drift() is None
    # corrupt: a reservation nothing owns (the leak signature)
    engine._pool.alloc(1)
    # ... but the same pool state mid-prefill is NOT a leak: the
    # admission holds its reservation across its chunks before its slot
    # goes active (the loop sleeps on its condition, so it never sees
    # this stand-in)
    engine._pf = object()
    assert engine.pool_drift() is None
    # ... and that same in-flight admission IS live work to the stall
    # check: its request is off the queue with no slot active yet, so a
    # wedged chunk would otherwise be invisible
    assert engine.health()["live_seqs"] == 1
    engine._pf = None
    assert engine.health()["live_seqs"] == 0
    assert wd.check_once() == []                  # first sighting
    fired = wd.check_once()                       # persisted -> trip
    assert len(fired) == 1
    assert "live block" in fired[0] and "zero live sequences" in fired[0]
    assert wd.trips[0][0] == "pool_drift"
