"""Fault-tolerant serving fleet: router, replicas, chaos, recovery.

The acceptance contract of the serving-fleet PR (docs/SERVING.md,
"Serving fleet"):

* **no request is lost** — a replica killed mid-generation has its
  in-flight set drained into the retry queue and replayed on survivors;
  every accepted request resolves (``requests_lost == 0``);
* **replay is bit-identical** — decode is deterministic greedy, so the
  re-dispatched output equals the fault-free run byte for byte;
* **liveness is observed** — a dead replica is flagged off heartbeat
  age (within 2 heartbeat intervals + scheduler slack), and a restarted
  one is readmitted only through the half-open ping/pong probe;
* **overload degrades loudly** — past the aggregate queue cap submit
  sheds ``OverloadedError(what="fleet")`` instead of queueing
  unboundedly, and with N-1 replicas the fleet keeps serving.

Unit tests run real wire + fake engines (deterministic, instant); the
replay-determinism test runs real engines in-process; the acceptance
test runs real subprocess replicas with a seeded ``os._exit`` kill.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _KV:
    """The three client calls the wire uses, over a local dict."""

    def __init__(self):
        self._d = {}
        self._cv = threading.Condition()

    def key_value_set(self, key, val, allow_overwrite=False):
        with self._cv:
            self._d[key] = val
            self._cv.notify_all()

    def blocking_key_value_get(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        with self._cv:
            while key not in self._d:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"NOT_FOUND: {key}")
                self._cv.wait(left)
            return self._d[key]

    def key_value_try_get(self, key):
        with self._cv:
            if key not in self._d:
                raise KeyError(f"NOT_FOUND: {key}")
            return self._d[key]


class _FakeEngine:
    """Deterministic instant 'decode': output is a pure function of the
    prompt, so replay determinism holds trivially and the router logic
    is what the test exercises."""

    def __init__(self, delay_s=0.0, queue_depth=0, fail_with=None):
        self.delay_s = delay_s
        self.queue_depth = queue_depth
        self.fail_with = fail_with
        self.submits = 0
        self.dead = False

    def submit(self, prompt, max_new=None, ctx=None):
        self.submits += 1
        if self.fail_with is not None:
            raise self.fail_with
        f = Future()
        p = np.asarray(prompt, np.int32)
        out = ((p[-1] + 1 + np.arange(max_new or 4)) % 64).astype(np.int32)

        def later():
            if self.delay_s:
                time.sleep(self.delay_s)
            if not self.dead:
                f.set_result({"result": out, "snapshot_version": 1,
                              "staleness_s": 0.0})

        if self.delay_s:
            threading.Thread(target=later, daemon=True).start()
        else:
            later()
        return f

    def health(self):
        return {"queue_depth": self.queue_depth, "live_seqs": 0}

    def stats(self):
        return {"submits": self.submits}

    def stop(self):
        pass


def _mk_fleet(label, n_replicas=3, hb_ms=50, engines=None, **cfg_kw):
    from multiverso_tpu.serving import (FleetConfig, FleetRouter,
                                        ReplicaServer)

    kv = _KV()
    size = n_replicas + 1
    cfg_kw.setdefault("deadline_s", 30.0)
    router = FleetRouter(size, kv, label=label, name=label,
                         fleet_config=FleetConfig(heartbeat_ms=hb_ms,
                                                  **cfg_kw))
    engines = engines or [_FakeEngine() for _ in range(n_replicas)]
    replicas = [ReplicaServer(r + 1, size, kv, engines[r], label=label,
                              heartbeat_ms=hb_ms)
                for r in range(n_replicas)]
    deadline = time.monotonic() + 20
    while router.stats()["up"] < n_replicas:
        assert time.monotonic() < deadline, router.replica_rows()
        time.sleep(0.01)
    return kv, router, replicas, engines


def _stop_fleet(router, replicas):
    router.stop()
    for rep in replicas:
        try:
            rep.stop()
        except Exception:
            pass


# -- fault plan ---------------------------------------------------------------

def test_fault_plan_parses_every_point():
    from multiverso_tpu.serving import FaultPlan

    plan = FaultPlan("kill_at_request=5, wedge_at_request=3:0.25, "
                     "wire_delay=0.05:0.5, wire_drop=0.1, "
                     "slow_heartbeat=4", seed=7)
    assert plan.kill_at == 5
    assert (plan.wedge_at, plan.wedge_s) == (3, 0.25)
    assert (plan.delay_s, plan.delay_p) == (0.05, 0.5)
    assert plan.drop_p == 0.1
    assert plan.heartbeat_scale == 4.0
    assert plan.active()
    assert not FaultPlan("").active()
    with pytest.raises(ValueError):
        FaultPlan("explode=1")
    with pytest.raises(ValueError):
        FaultPlan("kill_at_request")
    with pytest.raises(ValueError):
        FaultPlan("slow_heartbeat=0.5")


def test_fault_plan_seed_replays_identical_schedule():
    from multiverso_tpu.serving import FaultPlan

    def roll(seed):
        plan = FaultPlan("wire_delay=0.01:0.5, wire_drop=0.3", seed=seed)
        return ([plan.wire_delay_s() for _ in range(50)],
                [plan.drop_heartbeat() for _ in range(50)])

    assert roll(3) == roll(3)               # deterministic replay
    assert roll(3) != roll(4)               # and actually seeded


def test_fault_plan_kill_fn_and_wedge():
    from multiverso_tpu.serving import FaultPlan

    killed = []
    plan = FaultPlan("kill_at_request=2, wedge_at_request=3:0.125",
                     kill_fn=lambda: killed.append(True))
    assert plan.on_request(1) == 0.0
    plan.on_request(2)
    assert killed == [True]
    assert plan.on_request(3) == 0.125
    assert plan.counts["kills"] == 1 and plan.counts["wedges"] == 1


# -- backoff schedules --------------------------------------------------------

def test_retry_backoff_schedule_and_jitter():
    import random

    from multiverso_tpu.serving import retry_backoff_s

    # deterministic ceiling: doubling from base, capped
    assert retry_backoff_s(1, 0.02, 1.0) == pytest.approx(0.02)
    assert retry_backoff_s(2, 0.02, 1.0) == pytest.approx(0.04)
    assert retry_backoff_s(5, 0.02, 1.0) == pytest.approx(0.32)
    assert retry_backoff_s(12, 0.02, 1.0) == pytest.approx(1.0)  # cap
    # huge attempt counts stay at the cap instead of overflowing the
    # float exponent (a request could in principle retry for hours)
    assert retry_backoff_s(5000, 0.02, 1.0) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        retry_backoff_s(0, 0.02, 1.0)
    # jitter: inside [ceiling/2, ceiling], not constant
    rng = random.Random(1)
    vals = [retry_backoff_s(3, 0.02, 1.0, rng) for _ in range(64)]
    assert all(0.04 <= v <= 0.08 for v in vals)
    assert len(set(vals)) > 1


# -- routing ------------------------------------------------------------------

def test_dispatch_completes_and_session_affinity():
    kv, router, replicas, engines = _mk_fleet("aff")
    try:
        outs = [router.predict(np.arange(1, 5, dtype=np.int32), 4,
                               session="sess-A") for _ in range(6)]
        served = {o["replica"] for o in outs}
        assert len(served) == 1            # affinity: one replica
        # a session-less burst spreads by load once one replica is busy
        for o in outs:
            assert o["result"].shape == (4,)
        st = router.stats()
        assert st["completed"] == 6 and st["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_least_loaded_dispatch_avoids_busy_replica():
    engines = [_FakeEngine(queue_depth=50), _FakeEngine(), _FakeEngine()]
    kv, router, replicas, _ = _mk_fleet("load", engines=engines)
    try:
        served = {router.predict(np.arange(1, 4, dtype=np.int32),
                                 3)["replica"] for _ in range(8)}
        assert 1 not in served             # rank 1 reports a deep queue
    finally:
        _stop_fleet(router, replicas)


def test_fleet_shed_past_aggregate_depth():
    from multiverso_tpu.serving import OverloadedError

    engines = [_FakeEngine(delay_s=5.0) for _ in range(2)]
    kv, router, replicas, _ = _mk_fleet("shed", n_replicas=2,
                                        engines=engines, shed_depth=4,
                                        deadline_s=60.0)
    try:
        futs = [router.submit(np.arange(1, 3, dtype=np.int32), 2)
                for _ in range(4)]
        with pytest.raises(OverloadedError) as exc:
            router.submit(np.arange(1, 3, dtype=np.int32), 2)
        assert exc.value.what == "fleet"
        assert router.stats()["shed"] == 1
        for f in futs:
            f.cancel()
    finally:
        _stop_fleet(router, replicas)


def test_deadline_exceeded_fails_the_future():
    from multiverso_tpu.serving import DeadlineExceededError

    engines = [_FakeEngine(delay_s=10.0)]
    kv, router, replicas, _ = _mk_fleet("dl", n_replicas=1,
                                        engines=engines)
    try:
        fut = router.submit(np.arange(1, 3, dtype=np.int32), 2,
                            deadline_s=0.2)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=10)
        assert router.stats()["deadline_failures"] == 1
        assert router.stats()["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_engine_error_fails_without_retry_storm():
    engines = [_FakeEngine(fail_with=ValueError("bad prompt")),
               _FakeEngine()]
    kv, router, replicas, _ = _mk_fleet("err", n_replicas=2,
                                        engines=engines)
    try:
        # pin to the failing replica via affinity warm-up is racy;
        # instead fail ALL of them: a deterministic error must not be
        # retried into a storm
        engines[1].fail_with = ValueError("bad prompt")
        fut = router.submit(np.arange(1, 3, dtype=np.int32), 2)
        with pytest.raises(RuntimeError, match="bad prompt"):
            fut.result(timeout=10)
        assert engines[0].submits + engines[1].submits == 1
    finally:
        _stop_fleet(router, replicas)


def test_replica_overload_is_retried_elsewhere():
    from multiverso_tpu.serving import OverloadedError

    engines = [_FakeEngine(fail_with=OverloadedError("e", 9, 8)),
               _FakeEngine()]
    kv, router, replicas, _ = _mk_fleet("ovl", n_replicas=2,
                                        engines=engines)
    try:
        got = set()
        for _ in range(4):
            got.add(router.predict(np.arange(1, 3, dtype=np.int32),
                                   2)["replica"])
        assert got == {2}                  # every shed retried onto r2
        assert router.stats()["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_shed_by_class_evicts_lowest_pending():
    """Past the aggregate cap the fleet sheds the LOWEST class first:
    a class-2 arrival evicts the newest queued class-0 request (whose
    future gets the retriable OverloadedError) instead of being
    rejected itself; only when nothing lower is queued does the
    arrival shed."""
    from multiverso_tpu.serving import (FleetConfig, FleetRouter,
                                        OverloadedError)

    kv = _KV()
    # no replicas ever come up: everything accepted stays PENDING,
    # which is exactly the state class-shedding arbitrates
    router = FleetRouter(3, kv, label="shedcls", name="shedcls",
                         fleet_config=FleetConfig(heartbeat_ms=50,
                                                  shed_depth=3,
                                                  deadline_s=60.0))
    try:
        lows = [router.submit(np.arange(1, 3, dtype=np.int32), 2,
                              priority=0) for _ in range(3)]
        hi = router.submit(np.arange(1, 3, dtype=np.int32), 2,
                           priority=2)
        with pytest.raises(OverloadedError) as exc:
            lows[-1].result(timeout=10)     # the NEWEST class-0 paid
        assert exc.value.retriable is True
        assert exc.value.what == "fleet"
        assert not hi.done()                # the class-2 arrival queued
        s = router.stats()
        assert s["shed_by_class"] == {"p0": 1}
        assert s["requests_lost"] == 0
        with pytest.raises(OverloadedError):
            router.submit(np.arange(1, 3, dtype=np.int32), 2,
                          priority=0)       # nothing lower: self-shed
        assert router.stats()["shed_by_class"] == {"p0": 2}
        for f in lows[:2] + [hi]:
            f.cancel()
    finally:
        router.stop()


def test_retry_backoff_past_deadline_fails_fast():
    """The retry queue respects deadlines: a backoff that would land
    past the request's deadline fails NOW with DeadlineExceededError
    instead of burning the wait on an answer nobody will read."""
    from multiverso_tpu.serving import DeadlineExceededError, OverloadedError

    engines = [_FakeEngine(fail_with=OverloadedError("e", 9, 8))]
    kv, router, replicas, _ = _mk_fleet(
        "dlretry", n_replicas=1, engines=engines,
        backoff_ms=1000.0, backoff_cap_ms=1000.0, deadline_s=0.3)
    try:
        fut = router.submit(np.arange(1, 3, dtype=np.int32), 2)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=10)
        s = router.stats()
        assert s["deadline_failures"] == 1
        assert s["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_nonretriable_shed_fails_without_burning_retries():
    """A replica's retriable=False shed (request bigger than its whole
    KV pool) fails the request immediately — exactly ONE dispatch, no
    retry storm against an impossibility."""
    from multiverso_tpu.serving import OverloadedError

    engines = [_FakeEngine(fail_with=OverloadedError(
        "e", 9, 2, what="kv block pool", retriable=False)),
        _FakeEngine(fail_with=OverloadedError(
            "e", 9, 2, what="kv block pool", retriable=False))]
    kv, router, replicas, _ = _mk_fleet("permshed", n_replicas=2,
                                        engines=engines)
    try:
        fut = router.submit(np.arange(1, 3, dtype=np.int32), 2)
        with pytest.raises(OverloadedError) as exc:
            fut.result(timeout=10)
        assert exc.value.retriable is False
        assert engines[0].submits + engines[1].submits == 1
        assert router.stats()["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


class _PrioRecordingEngine(_FakeEngine):
    """Fake engine with the PRIORITY-aware submit surface: records the
    (priority, deadline_s) the replica handed it."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.seen = []

    def submit(self, prompt, max_new=None, ctx=None, priority=None,
               deadline_s=None):
        self.seen.append((priority, deadline_s))
        return super().submit(prompt, max_new, ctx)


def test_priority_and_deadline_ride_the_wire():
    """submit(priority=) crosses the mvserve wire and lands in the
    replica engine's submit as the same class, with the REMAINING
    deadline budget re-anchored on the replica's clock."""
    engines = [_PrioRecordingEngine()]
    kv, router, replicas, _ = _mk_fleet("priowire", n_replicas=1,
                                        engines=engines,
                                        deadline_s=30.0)
    try:
        reply = router.predict(np.arange(1, 3, dtype=np.int32), 2,
                               priority=3)
        assert reply["replica"] == 1
        assert len(engines[0].seen) == 1
        prio, deadline_s = engines[0].seen[0]
        assert prio == 3
        assert deadline_s is not None and 0 < deadline_s <= 30.0
    finally:
        _stop_fleet(router, replicas)


# -- death, redispatch, readmission -------------------------------------------

def test_dead_replica_flagged_drained_and_survivors_serve():
    hb_ms = 60
    engines = [_FakeEngine(delay_s=0.5), _FakeEngine(delay_s=0.01),
               _FakeEngine(delay_s=0.01)]
    kv, router, replicas, _ = _mk_fleet("death", hb_ms=hb_ms,
                                        engines=engines)
    try:
        # pin a session to rank 1 (slowest, but all start empty: force
        # it by loading the others first)
        engines[1].queue_depth = engines[2].queue_depth = 50
        time.sleep(3 * hb_ms / 1000.0)      # heartbeats carry the load
        futs = [router.submit(np.arange(1, 5, dtype=np.int32), 4,
                              session="pin") for _ in range(3)]
        time.sleep(0.05)                    # in flight on rank 1
        assert router._affinity.get("pin") == 1
        t_kill = time.monotonic()
        replicas[0].die()
        # flagged DEAD within 2 heartbeat intervals (+ scheduler slack)
        while router.replica_rows()[0]["state"] != "DEAD":
            assert time.monotonic() - t_kill < 5.0, router.replica_rows()
            time.sleep(0.002)
        detect_s = time.monotonic() - t_kill
        assert detect_s < 2 * hb_ms / 1000.0 + 1.0, detect_s
        # every in-flight request replays on survivors and completes
        outs = [f.result(timeout=20) for f in futs]
        assert {o["replica"] for o in outs} <= {2, 3}
        st = router.stats()
        assert st["requests_lost"] == 0
        assert st["deaths"] == 1
        assert st["recovery_time_s"] is not None
        # affinity pin moved off the corpse
        assert router._affinity.get("pin") != 1
    finally:
        _stop_fleet(router, replicas)


def test_half_open_readmission_probe():
    from multiverso_tpu.serving import ReplicaServer

    hb_ms = 50
    kv, router, replicas, engines = _mk_fleet("readmit", hb_ms=hb_ms)
    try:
        replicas[0].die()
        while router.replica_rows()[0]["state"] != "DEAD":
            time.sleep(0.005)
        # restart the rank: heartbeats resume -> PROBING -> ping/pong
        # round-trip -> UP; no real request lands before the pong
        replicas[0] = ReplicaServer(1, 4, kv, _FakeEngine(),
                                    label="readmit", heartbeat_ms=hb_ms)
        deadline = time.monotonic() + 10
        while router.stats()["readmissions"] < 1:
            assert time.monotonic() < deadline, router.replica_rows()
            time.sleep(0.005)
        rows = router.replica_rows()
        assert rows[0]["state"] == "UP"
        assert rows[0]["readmissions"] == 1
        # the readmitted replica serves again
        served = {router.predict(np.arange(1, 4, dtype=np.int32),
                                 3)["replica"] for _ in range(6)}
        assert 1 in served
        assert router.stats()["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_n_minus_one_keeps_serving_at_reduced_capacity():
    kv, router, replicas, _ = _mk_fleet("degraded")
    try:
        replicas[2].die()
        while router.replica_rows()[2]["state"] != "DEAD":
            time.sleep(0.005)
        outs = [router.predict(np.arange(1, 4, dtype=np.int32), 3)
                for _ in range(6)]
        assert {o["replica"] for o in outs} <= {1, 2}
        st = router.stats()
        assert st["up"] == 2 and st["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_retry_budget_exhaustion_fails_loudly():
    from multiverso_tpu.serving import FleetError, OverloadedError

    engines = [_FakeEngine(fail_with=OverloadedError("e", 9, 8))]
    kv, router, replicas, _ = _mk_fleet("budget", n_replicas=1,
                                        engines=engines, retry_max=2,
                                        backoff_ms=5.0,
                                        backoff_cap_ms=10.0)
    try:
        fut = router.submit(np.arange(1, 3, dtype=np.int32), 2)
        with pytest.raises(FleetError):
            fut.result(timeout=10)
        assert engines[0].submits == 3      # first + retry_max replays
        assert router.stats()["requests_lost"] == 0
    finally:
        _stop_fleet(router, replicas)


def test_slow_heartbeat_chaos_applies_after_assignment():
    """Review finding: heartbeat_scale used to be folded into the
    interval at construction, so the bench/test idiom of assigning
    ``replica.chaos = FaultPlan(...)`` AFTER construction made a
    slow_heartbeat plan a silent no-op. The scale is now read per
    beat."""
    from multiverso_tpu.serving import FaultPlan

    kv, router, replicas, _ = _mk_fleet("slowhb", n_replicas=1,
                                        hb_ms=40)
    try:
        rep = replicas[0]
        deadline = time.monotonic() + 10
        while rep.heartbeats < 5:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        rep.chaos = FaultPlan("slow_heartbeat=100")   # 40ms -> 4s
        time.sleep(0.2)                   # drain the in-flight wait
        n0 = rep.heartbeats
        time.sleep(0.6)
        assert rep.heartbeats - n0 <= 1   # ~15 beats without the scale
    finally:
        _stop_fleet(router, replicas)


def test_boot_dead_replica_does_not_pin_release_frontier():
    """Review finding: a replica that never manages a first heartbeat
    (crashed at boot) stays CONNECTING forever, and its ack (0) used
    to pin the router's request-stream release frontier at 0 — the
    retained window then grew by one record per dispatch, unbounded.
    Never-connected ranks are excluded like DEAD ones."""
    from multiverso_tpu.serving import FleetRouter, ReplicaServer

    kv2 = _KV()
    router2 = FleetRouter(4, kv2, label="bootdead2", name="bootdead2")
    live = [ReplicaServer(r, 4, kv2, _FakeEngine(), label="bootdead2")
            for r in (1, 2)]                      # rank 3 never boots
    try:
        deadline = time.monotonic() + 20
        while router2.stats()["up"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        for i in range(6):
            router2.predict(np.arange(1, 4, dtype=np.int32), 3)
        # the live replicas' acks drive the frontier forward even
        # though rank 3 (CONNECTING, no heartbeat ever) never acks
        deadline = time.monotonic() + 10
        while router2._released == 0:
            assert time.monotonic() < deadline, (
                router2._released, router2._seq)
            time.sleep(0.02)
        with router2._transport._lock:
            retained = len(router2._transport._retained)
        assert retained < router2._seq    # window actually drained
    finally:
        router2.stop()
        for rep in live:
            rep.stop()


# -- tracing ------------------------------------------------------------------

def test_route_dispatch_span_links_router_to_replica():
    from multiverso_tpu import trace

    trace.enable(4096)
    try:
        kv, router, replicas, _ = _mk_fleet("spans", n_replicas=1)
        try:
            router.predict(np.arange(1, 4, dtype=np.int32), 3)
        finally:
            _stop_fleet(router, replicas)
        spans = trace.collector().spans()
        by_name = {}
        for sp in spans:
            by_name.setdefault(sp.name, []).append(sp)
        roots = [sp for sp in by_name.get("serve.request", [])
                 if sp.attrs.get("fleet")]
        assert roots, sorted(by_name)
        root = roots[0]
        dispatch = [sp for sp in by_name.get("route.dispatch", [])
                    if sp.trace_id == root.trace_id]
        assert dispatch and dispatch[0].parent_id == root.span_id
        # the replica's span rides the SAME trace id across the wire
        execs = [sp for sp in by_name.get("replica.exec", [])
                 if sp.trace_id == root.trace_id]
        assert execs and execs[0].parent_id == dispatch[0].span_id
    finally:
        trace.disable()


# -- opscenter replica rows ---------------------------------------------------

def test_collector_table_renders_replica_rows():
    from multiverso_tpu.serving.obs_plane import ObsCollector

    col = ObsCollector()
    col.ingest(0, {"v": 1, "node": 0, "seq": 0, "ts": 1.0, "rows": {
        "FLEET_REPLICA_STATE[fleet.1]": {"type": "gauge", "value": 3},
        "FLEET_INFLIGHT[fleet.1]": {"type": "gauge", "value": 2},
        "FLEET_HB_AGE_MS[fleet.1]": {"type": "gauge", "value": 41.5},
        "FLEET_SNAPSHOT_VERSION[fleet.1]": {"type": "gauge",
                                            "value": 17},
        "FLEET_REPLICA_STATE[fleet.2]": {"type": "gauge", "value": 0},
        "FLEET_INFLIGHT[fleet.2]": {"type": "gauge", "value": 0},
        "FLEET_HB_AGE_MS[fleet.2]": {"type": "gauge", "value": 912.0},
    }})
    rows = col.replica_rows()
    assert [(r["replica"], r["state"], r["inflight"]) for r in rows] == [
        ("fleet.1", "UP", 2), ("fleet.2", "DEAD", 0)]
    # served snapshot version per replica; a pre-PR 14 archive lacking
    # the gauge renders -1 (tolerance pattern) — a fleet serving
    # divergent or frozen versions is visible at a glance
    assert [r["snapshot_version"] for r in rows] == [17, -1]
    table = col.table()
    assert "fleet.1" in table and "UP" in table
    assert "fleet.2" in table and "DEAD" in table
    assert "hb_age_ms" in table and "snap_v" in table
    assert "17" in table


def test_live_router_gauges_feed_the_obs_report():
    """The router's per-replica gauges ride the standard Dashboard
    snapshot, so the obs plane ships them with zero fleet-specific
    wiring — the collector's replica_rows() reads them back."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.serving.obs_plane import ObsCollector

    kv, router, replicas, _ = _mk_fleet("gauges", n_replicas=2)
    try:
        snap = Dashboard.snapshot()
        rows = {k: v for k, v in snap.items() if "gauges." in k}
        col = ObsCollector()
        col.ingest(0, {"v": 1, "node": 0, "seq": 0, "ts": 1.0,
                       "rows": rows})
        got = col.replica_rows()
        assert {r["replica"] for r in got} == {"gauges.1", "gauges.2"}
        assert all(r["state"] == "UP" for r in got)
    finally:
        _stop_fleet(router, replicas)


# -- replay determinism with REAL engines -------------------------------------

def _small_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                d_ff=64, max_seq=32)
    base.update(kw)
    return TransformerConfig(**base)


def test_replay_determinism_real_engines_kill_mid_generation(mv_session):
    """The tentpole invariant, end to end in one process: a 3-replica
    fleet of REAL decode engines serves a trace twice — fault-free,
    then with a chaos kill dropping one replica mid-generation. Every
    request completes both times and the outputs are byte-identical
    (deterministic greedy decode + replay-from-prompt)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import (FaultPlan, FleetConfig,
                                        FleetRouter, ReplicaServer)
    from multiverso_tpu.serving.decode_engine import (DecodeEngine,
                                                      DecodeEngineConfig)

    cfg = _small_cfg()
    engines = []
    for r in range(3):
        engine = DecodeEngine(f"flt{r}", TransformerLM(cfg),
                              DecodeEngineConfig(
                                  slots=2, max_prompt=8, max_new=10,
                                  watchdog=False))
        engine.warmup()
        engines.append(engine)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, cfg.vocab_size,
                          int(rng.integers(2, 9))).astype(np.int32),
             int(rng.integers(4, 11))) for _ in range(12)]
    runs = {}
    try:
        for label, chaos in (("clean", ""), ("chaos",
                                             "kill_at_request=2")):
            kv = _KV()
            # a DEAD verdict after 50 silent heartbeat periods, not
            # the default 2: this test counts deaths, and on a shared
            # CPU a heartbeat 120 ms late is a starved thread (a
            # survivor flapped and the count read 2)
            router = FleetRouter(4, kv, label=f"replay_{label}",
                                 fleet_config=FleetConfig(
                                     heartbeat_ms=60, dead_after_s=3.0,
                                     deadline_s=120.0))
            replicas = [ReplicaServer(r + 1, 4, kv, engines[r],
                                      label=f"replay_{label}",
                                      heartbeat_ms=60)
                        for r in range(3)]
            if chaos:
                replicas[0].chaos = FaultPlan(
                    chaos, kill_fn=replicas[0].die)
            deadline = time.monotonic() + 30
            while router.stats()["up"] < 3:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            futs = [router.submit(p, m, session=f"s{i % 4}")
                    for i, (p, m) in enumerate(reqs)]
            runs[label] = [np.asarray(f.result(timeout=120)["result"],
                                      np.int32) for f in futs]
            st = router.stats()
            assert st["requests_lost"] == 0, st
            assert st["output_mismatches"] == 0, st
            if chaos:
                assert st["deaths"] == 1, st
            router.stop()
            for rep in replicas:
                rep.stop(stop_engine=False)
    finally:
        for engine in engines:
            engine.stop()
    for i, (clean, chaos) in enumerate(zip(runs["clean"], runs["chaos"])):
        assert clean.shape == chaos.shape, i
        assert np.array_equal(clean, chaos), i


# -- the real 3-process chaos acceptance test ---------------------------------

_REPLICA_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, %r)
    import numpy as np

    rank = int(os.environ["FLEET_RANK"])
    root = os.environ["FLEET_ROOT"]
    chaos = os.environ.get("FLEET_CHAOS", "")

    class FileKV:
        def _p(self, key):
            return os.path.join(root, "kv", key.replace("/", "_"))
        def key_value_set(self, key, val, allow_overwrite=False):
            p = self._p(key); tmp = p + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(str(val))
            os.replace(tmp, p)
        def blocking_key_value_get(self, key, timeout_ms):
            deadline = time.monotonic() + timeout_ms / 1000.0
            while True:
                try:
                    with open(self._p(key)) as f:
                        return f.read()
                except FileNotFoundError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(key)
                    time.sleep(0.02)
        def key_value_try_get(self, key):
            try:
                with open(self._p(key)) as f:
                    return f.read()
            except FileNotFoundError:
                raise KeyError("NOT_FOUND: " + key)

    import multiverso_tpu as mv
    # the flag-wired bootstrap path: -chaos/-chaos_seed arm the plan,
    # -fleet_heartbeat_ms paces the liveness signal
    mv.init(["w", "-log_level=error", "-fleet_heartbeat_ms=250",
             "-chaos=" + chaos, "-chaos_seed=1"])
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import serve_replica

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=32)
    replica = serve_replica(rank, 4, FileKV(), TransformerLM(cfg),
                            label="fleet",
                            engine_kw=dict(slots=2, max_prompt=8,
                                           max_new=10,
                                           watchdog=False))
    print(f"REPLICA{rank}_UP", flush=True)
    FileKV().blocking_key_value_get("phase/done", 300_000)
    replica.stop()
    mv.shutdown()
    print(f"REPLICA{rank}_CLEAN_EXIT", flush=True)
""")


def _spawn_replica(tmp_path, rank, chaos=""):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "FLEET_RANK": str(rank),
                "FLEET_ROOT": str(tmp_path), "FLEET_CHAOS": chaos,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    return subprocess.Popen([sys.executable, "-c",
                             _REPLICA_WORKER % _REPO], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def test_fleet_chaos_three_process_acceptance(tmp_path, mv_session):
    """The acceptance test: three real subprocess replicas (each a warm
    DecodeEngine on the mvserve wire), a seeded chaos kill
    (``os._exit`` mid-trace) of one replica, and a restart. Every
    submitted request completes, outputs are bit-identical to the
    per-request oracle (greedy_decode on the same seeded params —
    i.e. to a fault-free run), requests_lost == 0, the death is
    flagged within 2 heartbeat intervals (+ scheduler slack), and the
    restarted replica is readmitted through the half-open probe."""
    import jax.numpy as jnp

    from multiverso_tpu.serving import FleetConfig, FleetRouter
    from multiverso_tpu.serving.faultinject import KILL_EXIT

    class FileKV:
        def _p(self, key):
            return os.path.join(str(tmp_path), "kv",
                                key.replace("/", "_"))

        def key_value_set(self, key, val, allow_overwrite=False):
            p = self._p(key)
            tmp = p + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(str(val))
            os.replace(tmp, p)

        def blocking_key_value_get(self, key, timeout_ms):
            deadline = time.monotonic() + timeout_ms / 1000.0
            while True:
                try:
                    with open(self._p(key)) as f:
                        return f.read()
                except FileNotFoundError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(key)
                    time.sleep(0.02)

        def key_value_try_get(self, key):
            try:
                with open(self._p(key)) as f:
                    return f.read()
            except FileNotFoundError:
                raise KeyError("NOT_FOUND: " + key)

    os.makedirs(tmp_path / "kv")
    hb_s = 0.25
    # the trace AND its oracle outputs come first: computing the oracle
    # (greedy_decode compiles per shape) while the fleet is live would
    # starve the router thread's GIL for seconds — long enough to
    # transiently flag healthy replicas DEAD under full-suite load
    # (the verify-skill GIL caveat, observed in CI)
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   greedy_decode,
                                                   init_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=32)
    params = init_params(cfg)
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(1, 64,
                          int(rng.integers(2, 9))).astype(np.int32),
             int(rng.integers(4, 11))) for _ in range(15)]
    oracles = [np.asarray(greedy_decode(
        cfg, params, jnp.asarray(p[None]), jnp.asarray([len(p)]), m,
        None))[0] for p, m in reqs]
    kv = FileKV()
    router = FleetRouter(4, kv, label="fleet",
                         fleet_config=FleetConfig(heartbeat_ms=250,
                                                  deadline_s=240.0))
    procs = {r: _spawn_replica(
        tmp_path, r, chaos="kill_at_request=3" if r == 1 else "")
        for r in (1, 2, 3)}
    restarted = None
    try:
        deadline = time.monotonic() + 180
        while router.stats()["up"] < 3:
            assert time.monotonic() < deadline, router.replica_rows()
            for r, p in procs.items():
                assert p.poll() is None, (r, p.communicate()[0][-4000:])
            time.sleep(0.05)
        # the trace: sessions pin some load onto every replica; the
        # seeded kill fires when rank 1 dequeues its 3rd request
        futs = [router.submit(p, m, session=f"s{i % 6}")
                for i, (p, m) in enumerate(reqs)]
        # rank 1 dies by os._exit(KILL_EXIT) mid-trace
        assert procs[1].wait(timeout=180) == KILL_EXIT
        t_exit = time.monotonic()
        while router.replica_rows()[0]["state"] != "DEAD":
            assert time.monotonic() - t_exit < 30, router.replica_rows()
            time.sleep(0.005)
        detect_s = time.monotonic() - t_exit
        assert detect_s < 2 * hb_s + 2.0, detect_s
        # ALL submitted requests complete despite the death ...
        outs = [np.asarray(f.result(timeout=240)["result"], np.int32)
                for f in futs]
        st = router.stats()
        assert st["requests_lost"] == 0, st
        assert st["output_mismatches"] == 0, st
        assert st["deaths"] >= 1 and st["recovery_time_s"] is not None
        # ... with outputs bit-identical to the fault-free oracle
        # (greedy decode over the SAME seeded params every replica
        # initialized — the replay-determinism contract; oracles were
        # computed BEFORE the fleet came up)
        for (prompt, _), out, oracle in zip(reqs, outs, oracles):
            assert np.array_equal(out, oracle), prompt
        # restart rank 1 (no chaos): half-open probe readmits it. Poll
        # RANK 1 specifically — under load another replica can flap
        # DEAD->readmitted and satisfy a fleet-wide readmissions count
        restarted = _spawn_replica(tmp_path, 1, chaos="")
        deadline = time.monotonic() + 180
        while True:
            row = router.replica_rows()[0]
            if row["readmissions"] >= 1 and row["state"] == "UP":
                break
            assert time.monotonic() < deadline, router.replica_rows()
            assert restarted.poll() is None
            time.sleep(0.05)
        # and serves new work
        served = set()
        deadline = time.monotonic() + 120
        while 1 not in served and time.monotonic() < deadline:
            served.add(router.predict(np.arange(1, 5, dtype=np.int32),
                                      4, timeout_s=120)["replica"])
        assert 1 in served, served
        assert router.stats()["requests_lost"] == 0
    finally:
        kv.key_value_set("phase/done", "1")
        router.stop()
        outs = {}
        for r, p in list(procs.items()) + [(("1r"), restarted)]:
            if p is None:
                continue
            try:
                outs[r], _ = p.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                outs[r] = "TIMEOUT: " + p.communicate()[0]
    assert procs[1].returncode == KILL_EXIT
    for r in (2, 3):
        assert procs[r].returncode == 0, f"rank {r}:\n{outs[r][-4000:]}"
        assert f"REPLICA{r}_CLEAN_EXIT" in outs[r]
    assert restarted.returncode == 0, outs["1r"][-4000:]
