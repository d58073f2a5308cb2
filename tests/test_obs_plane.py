"""Fleet observability plane (serving/obs_plane.py) + its export tools.

The acceptance contract of the fleet-plane PR (docs/OBSERVABILITY.md
"Fleet plane"):

* **mergeable histograms** — ``Histogram.buckets()`` exports merge
  across nodes and any percentile read off the merged counts lands
  within the documented log-bucket error (``BUCKET_REL_ERROR``) of the
  pooled-sample nearest-rank truth, on randomized multi-node splits;
* **one delta semantics** — the wire reports and the JSONL
  ``MetricsExporter`` compute interval deltas through the SAME shared
  helper (``dashboard.snapshot_deltas``), so the two sinks can never
  drift;
* **exact fleet counters** — every row ships cumulative values, so the
  collector's fleet sum equals the sum of per-node dashboards exactly,
  regardless of delta loss or report coalescing;
* **degraded nodes are flagged, once per episode** — last-report age
  with the EngineWatchdog edge-trigger/re-arm semantics;
* **one merged fleet trace** — per-node span shipments assemble into a
  single Chrome/Perfetto doc (one process track per node) that passes
  ``validate_chrome_events`` even when trace ids collide across nodes
  or a cross-process parent link spans two pids;
* **a real 3-process fleet** — agents in three OS processes ship over
  the real p2p wire to the rank-0 collector: counter totals exact,
  merged p99 within the bucket bound, a silent node flagged DEGRADED,
  the merged trace valid, zero dropped reports — and the report
  archives replay through ``tools/opscenter.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from multiverso_tpu import trace  # noqa: E402
from multiverso_tpu.dashboard import (BUCKET_REL_ERROR, Dashboard,  # noqa: E402
                                      Histogram, MetricsExporter,
                                      bucket_breach_frac, bucket_percentile,
                                      merge_buckets, parse_prometheus,
                                      snapshot_deltas)
from multiverso_tpu.serving.obs_plane import (ObsAgent,  # noqa: E402
                                              ObsCollector)
from multiverso_tpu.trace import validate_chrome_events  # noqa: E402


def _nearest_rank(sorted_data, p):
    n = len(sorted_data)
    return sorted_data[min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))]


@pytest.fixture(autouse=True)
def _clean_dashboard():
    Dashboard.reset()
    yield
    Dashboard.reset()


# -- log-bucket export / merge ------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 23])
def test_bucket_merge_percentiles_within_documented_error(seed):
    """Randomized samples split across 3 simulated nodes: the merged
    p50/p99 must sit within the documented log-bucket error of the
    pooled-sample nearest-rank truth (the satellite's accuracy
    contract)."""
    rng = np.random.default_rng(seed)
    samples = rng.lognormal(mean=2.0, sigma=1.4, size=4500)
    parts = np.array_split(samples, 3)
    exports = []
    for i, part in enumerate(parts):
        h = Histogram(f"B{seed}_{i}", register=False)
        for v in part:
            h.record(float(v))
        exports.append(h.buckets())
    merged = merge_buckets(exports)
    # counts merge EXACTLY: every pooled sample lands in some bucket
    assert merged["zero"] + sum(merged["counts"].values()) == len(samples)
    pooled = sorted(samples)
    for p in (50.0, 95.0, 99.0):
        truth = _nearest_rank(pooled, p)
        est = bucket_percentile(merged, p)
        assert abs(est - truth) / truth <= BUCKET_REL_ERROR + 1e-9, (
            p, truth, est)


def test_bucket_export_zero_and_empty_cases():
    h = Histogram("BZ", register=False)
    assert bucket_percentile(h.buckets(), 99) == 0.0
    for v in (0.0, -1.0, 0.5, 8.0):
        h.record(v)
    ex = h.buckets()
    assert ex["zero"] == 2 and sum(ex["counts"].values()) == 2
    # rank 0/1 sit in the zero bucket, the top ranks in real buckets
    assert bucket_percentile(ex, 0) == 0.0
    assert bucket_percentile(ex, 99) == pytest.approx(8.0,
                                                      rel=BUCKET_REL_ERROR)
    # merge tolerates missing-node entries (None) and empty exports
    merged = merge_buckets([ex, None, Histogram("BE",
                                                register=False).buckets()])
    assert merged["zero"] == 2 and sum(merged["counts"].values()) == 2


def test_bucket_breach_frac_tracks_threshold():
    h = Histogram("BB", register=False)
    for v in (1.0, 2.0, 100.0, 200.0):
        h.record(v)
    ex = h.buckets()
    assert bucket_breach_frac(ex, 50.0) == pytest.approx(0.5)
    assert bucket_breach_frac(ex, 1e9) == 0.0
    assert bucket_breach_frac(ex, 0.0) == 1.0


# -- shared delta helper ------------------------------------------------------

def test_snapshot_deltas_is_the_exporter_semantics():
    """One delta semantics: the module helper and MetricsExporter._deltas
    (which now delegates to it) agree field-for-field, including the
    reset-mid-interval drop rule."""
    prev = {"C[x]": {"type": "counter", "value": 10},
            "H[x]": {"type": "histogram", "count": 4, "p50_ms": 1.0},
            "G[x]": {"type": "gauge", "value": 5.0}}
    snap = {"C[x]": {"type": "counter", "value": 25},
            "H[x]": {"type": "histogram", "count": 2, "p50_ms": 2.0},
            "G[x]": {"type": "gauge", "value": 9.0},
            "NEW[x]": {"type": "counter", "value": 3}}
    helper = snapshot_deltas(prev, snap, 2.0)
    exporter = MetricsExporter(interval_s=60)
    exporter._last = prev
    assert exporter._deltas(snap, 2.0) == helper
    assert helper["C[x]"] == {"value": 15, "value_per_s": 7.5}
    assert "H[x]" not in helper          # count went backwards: reset
    assert "G[x]" not in helper          # gauges are not monotonic
    assert "NEW[x]" not in helper        # absent from prev: next interval
    assert snapshot_deltas(None, snap, 2.0) == {}
    assert snapshot_deltas(prev, snap, 0.0) == {}


# -- agent reports (loopback) -------------------------------------------------

def test_agent_ships_changed_rows_deltas_and_buckets():
    c = Dashboard.get_or_create_counter("OBS_T_C[x]")
    c.inc(5)
    h = Dashboard.get_or_create_histogram("OBS_T_H[x]")
    h.record(10.0)
    agent = ObsAgent(report_ms=50, engines=lambda: {}, start=False)
    try:
        rep = agent.tick()
        assert rep["v"] == 1 and rep["seq"] == 0
        assert "OBS_T_C[x]" in rep["rows"] and "OBS_T_H[x]" in rep["rows"]
        assert "OBS_T_H[x]" in rep["buckets"]
        assert rep["deltas"] == {}           # no previous snapshot yet
        # second report: only what CHANGED ships, deltas ride the
        # shared helper
        time.sleep(0.02)
        c.inc(3)
        rep2 = agent.tick()
        assert rep2["seq"] == 1
        assert "OBS_T_C[x]" in rep2["rows"]
        assert "OBS_T_H[x]" not in rep2["rows"]       # unchanged
        assert "OBS_T_H[x]" not in rep2["buckets"]
        assert rep2["deltas"]["OBS_T_C[x]"]["value"] == 3
        # the loopback collector folded both reports; counters are the
        # CURRENT cumulative value, not an integral of deltas
        fl = agent.collector.fleet()
        assert fl["counters"]["OBS_T_C[x]"] == 8
    finally:
        agent.stop(final_report=False)


def test_agent_drains_spans_incrementally():
    trace.enable(256)
    try:
        agent = ObsAgent(report_ms=50, engines=lambda: {}, start=False)
        with trace.span("serve.request", root=True, model="m"):
            pass
        rep = agent.tick()
        assert len(rep["spans"]) == 1
        assert rep["spans"][0]["name"] == "serve.request"
        assert rep["spans_missed"] == 0
        rep2 = agent.tick()
        assert rep2["spans"] == []           # cursor advanced, no re-ship
        agent.stop(final_report=False)
    finally:
        trace.disable()
        trace.collector().clear()


def test_agent_forwards_watchdog_trips_exactly_once():
    """serving/watchdog.py -> collector forwarding: every trip rides
    exactly one report (the sequence-stamped trips_since cursor), and
    the collector keys them per node."""
    from multiverso_tpu.serving.watchdog import EngineWatchdog, \
        WatchdogConfig

    class FakeEngine:
        name = "fe"

        def stats(self):
            return {"tokens_per_s": 12.5, "live_seqs": 1, "completed": 3,
                    "shed": 0, "watchdog_trips": self.watchdog.trip_count
                    if self.watchdog else 0}

        def health(self):
            return {"live_seqs": 1, "stopped": False}

        def pool_drift(self):
            return None

        watchdog = None
        recorder = None

    eng = FakeEngine()
    eng.watchdog = EngineWatchdog(eng, WatchdogConfig(), start=False)
    agent = ObsAgent(report_ms=50, engines=lambda: {"fe": eng},
                     start=False)
    try:
        eng.watchdog._trip("stall", "r1")
        eng.watchdog._trip("queue_age", "r2")
        rep = agent.tick()
        wd = rep["engines"]["fe"]["watchdog"]
        assert wd["trips_total"] == 2
        assert [t[0] for t in wd["new_trips"]] == ["stall", "queue_age"]
        rep2 = agent.tick()
        assert rep2["engines"]["fe"]["watchdog"]["new_trips"] == []
        eng.watchdog._trip("stall", "r3")
        rep3 = agent.tick()
        assert [t[0] for t in
                rep3["engines"]["fe"]["watchdog"]["new_trips"]] == ["stall"]
        st = agent.collector.node_state(0)
        assert [t[1] for t in st["trips"]] == ["stall", "queue_age",
                                               "stall"]
        # engine surface rode along
        assert rep["engines"]["fe"]["stats"]["tokens_per_s"] == 12.5
        assert rep["engines"]["fe"]["health"]["live_seqs"] == 1
    finally:
        agent.stop(final_report=False)


# -- collector aggregation ----------------------------------------------------

def _report(node, seq, rows=None, buckets=None, spans=None, anchor=None,
            engines=None, ts=None):
    return {"v": 1, "node": node, "seq": seq, "ts": ts or float(seq),
            "mono": float(seq), "interval_s": 1.0, "rows": rows or {},
            "deltas": {}, "buckets": buckets or {},
            "engines": engines or {}, "spans": spans or [],
            "spans_missed": 0, "trace_anchor": anchor or [0.0, 0.0]}


def test_collector_sums_counters_exactly_and_merges_histograms():
    col = ObsCollector()
    rng = np.random.default_rng(3)
    all_samples = []
    for node in range(3):
        h = Histogram(f"CS{node}", register=False)
        samples = rng.lognormal(1.0, 1.0, 500)
        all_samples.extend(samples)
        for v in samples:
            h.record(float(v))
        rows = {
            "REQS[x]": {"type": "counter", "value": 100 + node},
            "LAT[x]": {"type": "histogram", "count": 500, "p50_ms": 0.0,
                       "p95_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0,
                       "max_ms": 0.0},
            "SLO_P99[LAT[x]]": {"type": "slo", "target_ms": 5.0,
                                "percentile": 99.0, "window": 500,
                                "value_ms": 0.0, "breach_frac": 0.0,
                                "burn": 0.0, "ok": 1},
        }
        col.ingest(node, _report(node, 0, rows=rows,
                                 buckets={"LAT[x]": h.buckets()}))
    fl = col.fleet()
    assert fl["nodes"] == 3
    assert fl["counters"]["REQS[x]"] == 303        # exact, not approximate
    pooled = sorted(all_samples)
    for p, key in ((50, "p50_ms"), (99, "p99_ms")):
        truth = _nearest_rank(pooled, p)
        est = fl["histograms"]["LAT[x]"][key]
        assert abs(est - truth) / truth <= BUCKET_REL_ERROR + 1e-9
    assert fl["histograms"]["LAT[x]"]["count"] == 1500
    # fleet SLO burn recomputed over the MERGED buckets
    slo = fl["slos"]["SLO_P99[LAT[x]]"]
    truth_breach = sum(v > 5.0 for v in pooled) / len(pooled)
    assert slo["breach_frac"] == pytest.approx(truth_breach, abs=0.05)
    assert slo["burn"] == pytest.approx(slo["breach_frac"] / 0.01)
    # a re-ingested row REPLACES (latest cumulative wins — lost deltas
    # never skew the sum)
    col.ingest(1, _report(1, 1, rows={
        "REQS[x]": {"type": "counter", "value": 150}}))
    assert col.fleet()["counters"]["REQS[x]"] == 100 + 150 + 102


def test_collector_merged_chrome_doc_validates_across_nodes():
    """Cross-node assembly: colliding trace ids on different nodes stay
    on separate process tracks; a cross-process parent link (publish on
    node 0, apply on node 1, one trace id) survives validation; each
    node's clock anchor rebases onto the shared epoch timebase."""
    col = ObsCollector()
    span0 = {"name": "serve.request", "trace_id": 7, "span_id": 1,
             "parent_id": None, "t0": 1.0, "t1": 2.0, "thread": "T",
             "attrs": {"model": "lm"}}
    pub = {"name": "bus.publish", "trace_id": 9, "span_id": 2,
           "parent_id": None, "t0": 2.0, "t1": 3.0, "thread": "T",
           "attrs": {}}
    # node 1: SAME trace id 7 (cross-node collision) + the apply half
    # of trace 9 parented under node 0's publish span
    span1 = {"name": "serve.request", "trace_id": 7, "span_id": 3,
             "parent_id": None, "t0": 0.5, "t1": 1.5, "thread": "T",
             "attrs": {"model": "lm"}}
    apply_ = {"name": "bus.apply", "trace_id": 9, "span_id": 4,
              "parent_id": 2, "t0": 2.5, "t1": 3.5, "thread": "T",
              "attrs": {}}
    col.ingest(0, _report(0, 0, spans=[span0, pub],
                          anchor=[1000.0, 0.0]))
    col.ingest(1, _report(1, 0, spans=[span1, apply_],
                          anchor=[1000.2, 0.0]))
    doc = col.export_chrome()
    events = doc["traceEvents"]
    summary = validate_chrome_events(events)
    assert summary["spans"] == 4
    pids = {e["pid"] for e in events if e.get("ph") == "B"}
    assert pids == {0, 1}                  # one process track per node
    names = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M"}
    assert names == {0: "node0", 1: "node1"}
    # clock rebase: node 1's anchor is 200 ms later, so its t0=0.5 span
    # starts at epoch 1000.7 s vs node 0's t0=1.0 at 1001.0 s
    b1 = [e for e in events if e.get("ph") == "B"
          and e["pid"] == 1 and e["name"] == "serve.request"][0]
    assert b1["ts"] == pytest.approx(1000.7e6)
    # the cross-process parent link survives (arg carried verbatim)
    ba = [e for e in events if e.get("ph") == "B"
          and e["name"] == "bus.apply"][0]
    assert ba["args"]["parent_id"] == "2"


def test_collector_degraded_edge_trigger_and_rearm():
    """FailureDetector-style last-report-age with EngineWatchdog
    re-arm: one event per episode, recovery re-arms, a second silence
    fires again."""
    clock = {"t": 0.0}
    fired = []
    col = ObsCollector(degraded_after_s=1.0,
                       on_degraded=lambda node, age: fired.append(node),
                       clock=lambda: clock["t"])
    col.ingest(0, _report(0, 0))
    col.ingest(1, _report(1, 0))
    clock["t"] = 0.5
    assert col.check() == [] and col.degraded() == []
    clock["t"] = 0.9
    col.ingest(0, _report(0, 1))
    clock["t"] = 1.5                      # node 1 is now 1.5s silent
    newly = col.check()
    assert [n for n, _ in newly] == [1]
    assert col.degraded() == [1] and fired == [1]
    # edge-triggered: the same episode never re-fires
    clock["t"] = 2.0
    col.ingest(0, _report(0, 2))
    assert col.check() == [] and fired == [1]
    # the degraded counter landed on the dashboard
    assert Dashboard.get_or_create_counter("OBS_DEGRADED[node1]"
                                           ).get() == 1
    # recovery re-arms and records its own event
    col.ingest(1, _report(1, 1))
    assert col.check() == [] and col.degraded() == []
    assert (1, "recovered") in {(n, kind) for n, kind, _ in col.events}
    # a SECOND silence is a new episode: it fires again
    clock["t"] = 4.0
    col.ingest(0, _report(0, 3))
    assert [n for n, _ in col.check()] == [1]
    assert fired == [1, 1]


def test_collector_prometheus_carries_node_label():
    col = ObsCollector()
    for node in range(2):
        col.ingest(node, _report(node, 0, rows={
            "REQS[x]": {"type": "counter", "value": 10 * (node + 1)}}))
    text = col.prometheus()
    assert 'node="0"' in text and 'node="1"' in text
    # one TYPE line per family even with per-node samples
    assert text.count("# TYPE mv_reqs counter") == 1
    # parse_prometheus (name-label keyed) still reads the samples
    assert "REQS[x]" in parse_prometheus(text)


def test_collector_table_lists_nodes_and_silence():
    col = ObsCollector()
    engines = {"lm": {"stats": {"tokens_per_s": 100.0, "live_seqs": 2,
                                "completed": 5, "shed": 0},
                      "health": {"live_seqs": 2},
                      "watchdog": {"trips_total": 1, "new_trips": []}}}
    col.ingest(0, _report(0, 0, engines=engines, ts=100.0))
    col.ingest(1, _report(1, 0, ts=90.0))   # trails the fleet by 10 s
    text = col.table(silent_after_s=5.0)
    assert "SILENT" in text and "ok" in text
    assert "100.0" in text                   # node 0's tok/s column
    lines = [ln for ln in text.splitlines() if ln.lstrip().startswith(
        ("0 ", "1 "))]
    assert len(lines) == 2


# -- the wire (in-process, real sockets) --------------------------------------

class _KV:
    """The three client calls the plane uses, backed by a local dict."""

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


def test_wire_reports_reach_collector_and_acks_release(tmp_path):
    """Three agents over real localhost p2p sockets in one process: the
    rank-0 collector keys all three nodes, acks drain the publish
    windows (no unbounded retention), and nothing is dropped. (The
    per-node REGISTRY split is the subprocess test's job — here all
    ranks share one process dashboard.)"""
    kv = _KV()
    c = Dashboard.get_or_create_counter("WIRE[x]")
    c.inc(5)
    agents = [ObsAgent(rank=r, size=3, client=kv, report_ms=60,
                       label=f"wt{os.getpid()}", engines=lambda: {},
                       start=False)
              for r in range(3)]
    try:
        deadline = time.monotonic() + 20
        col = agents[0].collector
        while True:
            for a in agents:
                a.tick()
            if (sorted(col.nodes()) == [0, 1, 2]
                    and col.fleet()["counters"].get("WIRE[x]") == 15):
                break
            assert time.monotonic() < deadline, col.stats()
            time.sleep(0.02)
        assert all(a.dropped_reports == 0 for a in agents)
        # acks released the non-collector publish windows
        for a in agents[1:]:
            deadline = time.monotonic() + 10
            while a._seq - a._released > 1:
                a.tick()
                assert time.monotonic() < deadline, (a._seq, a._released)
                time.sleep(0.02)
            with a._transport._lock:
                assert len(a._transport._retained) <= 1
    finally:
        for a in agents:
            a.stop(final_report=False)


def test_wire_drops_whole_reports_past_outstanding_cap():
    """A collector that stops consuming must bound the publisher: past
    MAX_OUTSTANDING un-acked reports the agent drops WHOLE reports and
    counts them instead of retaining without bound — and a drop must
    NOT consume the delta state (review finding): rows that changed and
    spans recorded during the drop window still ship, exactly once, in
    the first report after capacity frees."""
    kv = _KV()
    trace.enable(256)
    agent = ObsAgent(rank=1, size=2, client=kv, report_ms=60,
                     label=f"dt{os.getpid()}", engines=lambda: {},
                     start=False)
    try:
        c = Dashboard.get_or_create_counter("DROP_T[x]")
        c.inc(1)
        for _ in range(ObsAgent.MAX_OUTSTANDING):
            agent.tick()                     # nobody acks: rank 0 absent
        # the window is full: changes landing NOW ride no shipped report
        c.inc(41)
        with trace.span("serve.request", root=True, model="m"):
            pass
        for _ in range(5):
            assert agent.tick() is None      # dropped before building
        assert agent.dropped_reports == 5
        with agent._transport._lock:
            assert len(agent._transport._retained) == \
                ObsAgent.MAX_OUTSTANDING
        # acks catch up -> the next report carries EVERYTHING the drop
        # window would otherwise have lost
        kv.key_value_set(f"dt{os.getpid()}/ack/1", str(agent._seq))
        rep = agent.tick()
        assert rep is not None
        assert rep["rows"]["DROP_T[x]"]["value"] == 42
        assert [sp["name"] for sp in rep["spans"]] == ["serve.request"]
    finally:
        agent.stop(final_report=False)
        trace.disable()
        trace.collector().clear()


def test_agent_final_report_keeps_engines_after_discovery_goes_dark():
    """Review finding: Session.stop() empties the server registry
    BEFORE the teardown ships the obs agent's final report, so live
    discovery returns {} exactly when the terminal stats (and the last
    interval's watchdog trips) must ship. The agent caches the last
    non-empty discovery and reads the still-alive engine objects."""
    from multiverso_tpu.serving.watchdog import EngineWatchdog, \
        WatchdogConfig

    class FakeEngine:
        name = "fe"
        watchdog = None
        recorder = None

        def stats(self):
            return {"tokens_per_s": 1.0, "live_seqs": 0, "completed": 7,
                    "shed": 0, "watchdog_trips": 0}

        def health(self):
            return {"live_seqs": 0, "stopped": True}

        def pool_drift(self):
            return None

    eng = FakeEngine()
    eng.watchdog = EngineWatchdog(eng, WatchdogConfig(), start=False)
    engines = {"fe": eng}
    agent = ObsAgent(report_ms=50, engines=lambda: dict(engines),
                     start=False)
    try:
        agent.tick()
        # the registry empties (teardown), THEN a final-interval trip
        # lands, THEN the final report ships — it must still carry the
        # engine block and forward the trip
        engines.clear()
        eng.watchdog._trip("stall", "terminal")
        rep = agent.tick()
        assert "fe" in rep["engines"]
        assert rep["engines"]["fe"]["health"]["stopped"] is True
        assert [t[0] for t in
                rep["engines"]["fe"]["watchdog"]["new_trips"]] == ["stall"]
    finally:
        agent.stop(final_report=False)


def test_collector_roster_flags_never_reporting_node():
    """Review finding: a replica that dies BEFORE its first report was
    invisible (the collector only learned nodes from ingest). The
    roster seeds every expected rank with its silence clock started at
    seeding, so a boot-wedged node ages out and flags DEGRADED."""
    clock = {"t": 0.0}
    col = ObsCollector(degraded_after_s=1.0, clock=lambda: clock["t"])
    col.expect_nodes(range(3))
    assert col.nodes() == [0, 1, 2]
    col.ingest(0, _report(0, 0))
    col.ingest(1, _report(1, 0))
    clock["t"] = 0.5
    assert col.check() == []                  # grace: threshold not hit
    clock["t"] = 1.2
    col.ingest(0, _report(0, 1))
    col.ingest(1, _report(1, 1))
    assert [n for n, _ in col.check()] == [2]  # never reported once
    assert col.degraded() == [2]
    # seeding again never resets a node that HAS reported
    col.expect_nodes(range(3))
    assert col.node_state(0)["reports"] == 2


def test_wire_hub_topology_only_collector_subscribes():
    """Review finding: the full-mesh transport shipped every report to
    every peer (O(N^2) wire traffic + mandatory drain-and-discard).
    With the hub topology only the collector rank subscribes; a
    publisher rank spawns no subscriber threads and its inboxes stay
    empty."""
    kv = _KV()
    agents = [ObsAgent(rank=r, size=3, client=kv, report_ms=60,
                       label=f"hub{os.getpid()}", engines=lambda: {},
                       start=False)
              for r in range(3)]
    try:
        def sub_threads(agent):
            return [t.name for t in agent._transport._threads
                    if t.name.startswith("p2p-sub")]

        assert len(sub_threads(agents[0])) == 2       # collector: all peers
        assert sub_threads(agents[1]) == []
        assert sub_threads(agents[2]) == []
        # the plane still works end to end over the hub
        deadline = time.monotonic() + 20
        col = agents[0].collector
        while not all(r in col.nodes()
                      and col.node_state(r)["reports"] > 0
                      for r in range(3)):
            for a in agents:
                a.tick()
            assert time.monotonic() < deadline, col.stats()
            time.sleep(0.02)
        # publisher inboxes never fill: nothing subscribes them
        for a in agents[1:]:
            with a._transport._lock:
                assert all(not box for box in a._transport._in.values())
    finally:
        for a in agents:
            a.stop(final_report=False)


# -- trace_summary on a merged multi-node doc ---------------------------------

def test_trace_summary_groups_by_node_and_trace_id():
    """Regression (satellite): the per-request report grouped by trace
    id ALONE — on a multi-pid doc, colliding trace ids across nodes
    found 2 roots and silently dropped both requests. It must group by
    (node, trace id) and ship a node column."""
    import tools.trace_summary as ts

    col = ObsCollector()
    mk = lambda tid, sid, name, t0, t1, parent=None: {
        "name": name, "trace_id": tid, "span_id": sid,
        "parent_id": parent, "t0": t0, "t1": t1, "thread": "T",
        "attrs": {"model": "lm"} if name == "serve.request" else {}}
    col.ingest(0, _report(0, 0, anchor=[1000.0, 0.0], spans=[
        mk(7, 1, "serve.request", 0.0, 0.1),
        mk(7, 2, "queue.wait", 0.01, 0.02, parent=1)]))
    col.ingest(1, _report(1, 0, anchor=[1000.0, 0.0], spans=[
        mk(7, 3, "serve.request", 0.0, 0.08),
        mk(7, 4, "queue.wait", 0.01, 0.03, parent=3)]))
    doc = col.export_chrome()
    # go through the real file path the tool reads
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(doc, f)
        path = f.name
    try:
        spans = ts.load_host_spans(path)
        rows = ts.request_report(spans)
    finally:
        os.unlink(path)
    reqs = [r for r in rows if r["name"] == "serve.request"]
    assert len(reqs) == 2                       # both nodes' requests
    assert sorted(r["node"] for r in reqs) == [0, 1]
    assert all(r["queue_ms"] > 0 for r in reqs)


# -- the real 3-process fleet -------------------------------------------------

_FLEET_WORKER = textwrap.dedent("""
    import os, sys, time, json
    sys.path.insert(0, %r)
    import numpy as np
    from multiverso_tpu.dashboard import Dashboard, BUCKET_REL_ERROR
    from multiverso_tpu import trace
    from multiverso_tpu.serving.obs_plane import ObsAgent
    from multiverso_tpu.trace import validate_chrome_events

    rank = int(os.environ["OBS_RANK"])
    root = os.environ["OBS_ROOT"]

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

    kv = FileKV()
    INTERVAL_MS = 250

    # per-node instruments: deterministic so rank 0 can regenerate the
    # POOLED truth for the merged-percentile assertion
    c = Dashboard.get_or_create_counter("FLEET_REQS[w]")
    c.inc(100 + rank)
    h = Dashboard.get_or_create_histogram("FLEET_LAT[w]")
    rng = np.random.default_rng(1000 + rank)
    for v in rng.lognormal(1.5, 1.2, 400):
        h.record(float(v))
    Dashboard.set_slo("FLEET_LAT[w]", 20.0, 99)
    trace.enable(4096)
    with trace.span("serve.request", root=True, model=f"m{rank}"):
        time.sleep(0.005)

    agent = ObsAgent(rank=rank, size=3, client=kv,
                     report_ms=INTERVAL_MS, label="fleet",
                     engines=lambda: {},
                     sink=os.path.join(root, f"reports.{rank}.jsonl"))

    if rank == 2:
        # ship a few reports, then go SILENT (loop halted, process
        # alive) — the collector must flag node 2 DEGRADED off
        # last-report age, threshold 2 report intervals
        time.sleep(4 * INTERVAL_MS / 1000.0)
        agent._stop.set(); agent._thread.join(); agent._thread = None
        kv.key_value_set("phase/r2_silent", str(time.time()))
        kv.blocking_key_value_get("phase/done", 120_000)
        agent.stop(final_report=False)
        print("RANK2_OBS_OK", flush=True)
        sys.exit(0)

    if rank == 1:
        kv.blocking_key_value_get("phase/done", 120_000)
        agent.stop(final_report=False)
        print("RANK1_OBS_OK", flush=True)
        sys.exit(0)

    # rank 0: the collector node
    col = agent.collector
    deadline = time.monotonic() + 90
    def wait(pred, what):
        while not pred():
            assert time.monotonic() < deadline, (what, col.stats())
            time.sleep(0.05)
    wait(lambda: sorted(col.nodes()) == [0, 1, 2], "nodes")
    # counter-sum exactness: collector totals == sum of per-node
    # dashboards, exactly
    wait(lambda: col.fleet()["counters"].get("FLEET_REQS[w]") == 303,
         "counter sum")
    # merged fleet p99 within the documented log-bucket error of the
    # pooled-sample truth
    pooled = sorted(float(v) for r in range(3)
                    for v in np.random.default_rng(1000 + r
                                                   ).lognormal(1.5, 1.2,
                                                               400))
    def nearest(p):
        n = len(pooled)
        return pooled[min(n - 1, max(0, int(round(p / 100 * (n - 1)))))]
    fl = col.fleet()
    assert fl["histograms"]["FLEET_LAT[w]"]["count"] == 1200, fl
    for p, key in ((50, "p50_ms"), (99, "p99_ms")):
        est = fl["histograms"]["FLEET_LAT[w]"][key]
        truth = nearest(p)
        assert abs(est - truth) / truth <= BUCKET_REL_ERROR + 1e-9, (
            p, est, truth)
    assert "SLO_P99[FLEET_LAT[w]]" in fl["slos"], fl["slos"]
    # the silent node is flagged DEGRADED (threshold = 2 report
    # intervals; allow scheduler slack on the detection wall clock)
    t_silent = float(kv.blocking_key_value_get("phase/r2_silent",
                                               60_000))
    wait(lambda: 2 in col.degraded(), "degraded")
    detect_s = time.time() - t_silent
    assert detect_s < 20.0, detect_s
    ev = [e for e in col.events if e[0] == 2 and e[1] == "degraded"]
    assert ev and ev[0][2] >= 2 * INTERVAL_MS / 1000.0, ev
    # the merged cross-process Perfetto doc validates: one process
    # track per node, one serve.request root per (node, trace)
    wait(lambda: {0, 1, 2} <= {e.get("pid") for e in
                               col.export_chrome()["traceEvents"]
                               if e.get("ph") == "B"}, "spans")
    doc = col.export_chrome(os.path.join(root, "fleet_trace.json"))
    summary = validate_chrome_events(doc["traceEvents"],
                                     root_name="serve.request")
    assert summary["roots"] == 3, summary
    assert agent.dropped_reports == 0
    # keep reporting a little longer so the offline archives show a
    # clear silence gap for node 2 (the opscenter SILENT assertion)
    time.sleep(6 * INTERVAL_MS / 1000.0)
    with open(os.path.join(root, "fleet_ok.json"), "w") as f:
        json.dump({"detect_s": detect_s, "fleet": True}, f)
    kv.key_value_set("phase/done", "1")
    agent.stop(final_report=False)
    print("RANK0_OBS_OK", flush=True)
""")


def test_three_process_fleet_aggregation(tmp_path):
    """The acceptance test: three real OS processes, each with its own
    Dashboard/trace collector, ship reports over the real p2p wire
    (endpoint discovery + acks through a file-backed KV — the only
    client surface the transport uses). Rank 0 asserts exact counter
    totals, bucket-bounded merged p99, degraded-node flagging, and a
    valid merged Perfetto doc; the report archives then replay through
    tools/opscenter.py in-process."""
    os.makedirs(tmp_path / "kv")
    procs = []
    for rank in range(3):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "OBS_RANK": str(rank),
                    "OBS_ROOT": str(tmp_path),
                    "XLA_FLAGS": "--xla_force_host_platform_device_count"
                                 "=1"})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _FLEET_WORKER % _REPO], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for rank, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"rank {rank} timed out (fleet plane stalled)")
        outs.append(out)
    for rank, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f"rank {rank}:\n{out[-4000:]}"
        assert f"RANK{rank}_OBS_OK" in out
    assert (tmp_path / "fleet_ok.json").exists()
    assert (tmp_path / "fleet_trace.json").exists()

    # opscenter replays the very archives the agents wrote
    import tools.opscenter as oc

    archives = [str(tmp_path / f"reports.{r}.jsonl") for r in range(3)]
    reports, _ = oc.load_reports(archives)
    assert {r["node"] for r in reports} == {0, 1, 2}
    col = oc.build_collector(reports)
    assert col.fleet()["counters"]["FLEET_REQS[w]"] == 303
    # the silent node's archive simply ENDS early: the offline rule
    # flags it SILENT against the fleet's newest report
    table = col.table(silent_after_s=1.0)
    assert "SILENT" in table
    # CLI smoke: table, --prom, --trace all walk the real files
    assert oc.main(archives) == 0
    assert oc.main(archives + ["--prom"]) == 0
    merged = str(tmp_path / "opscenter_trace.json")
    assert oc.main(archives + ["--trace", merged]) == 0
    with open(merged) as f:
        doc = json.load(f)
    validate_chrome_events(doc["traceEvents"])
