"""Observability integration: instruments, export surface, traced serving.

The acceptance contract of the tracing/metrics PR (docs/OBSERVABILITY.md):

* **explain one request** — a traced serving request exports a Chrome
  trace in which ITS root span contains queue-wait, admission/prefill
  and per-iteration decode children, all under one trace id (the e2e
  smoke below validates structure: monotonic ts, matched B/E pairs, one
  root per request);
* **off = free** — tracing is disabled by default and the decode hot
  loop must not allocate a single trace object per iteration while off;
* **one snapshot, many sinks** — ``Dashboard.snapshot()`` round-trips
  through the JSON-lines reporter and the Prometheus text renderer with
  identical values;
* **instruments are trustworthy under concurrency** — Histogram record
  vs percentiles races (ring wrap-around included) never tear.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from multiverso_tpu import trace
from multiverso_tpu.dashboard import (Counter, Dashboard, Gauge, Histogram,
                                      MetricsExporter, parse_prometheus,
                                      render_prometheus)


@pytest.fixture()
def traced():
    trace.enable(65536)
    trace.collector().clear()
    yield trace.collector()
    trace.disable()
    trace.collector().clear()


def _wait(pred, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not pred():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.005)


# -- instruments -------------------------------------------------------------

def test_watch_resolves_every_instrument_kind():
    """Regression: watch() only looked at Monitors — a live Histogram or
    Gauge reported "not monitored"."""
    Dashboard.reset()
    hist = Dashboard.get_or_create_histogram("SERVE_TTFT[lm]")
    hist.record(12.5)
    gauge = Dashboard.get_or_create_gauge("SLOT_OCC[lm]")
    gauge.set(0.75)
    counter = Dashboard.get_or_create_counter("SERVE_SHED[lm]")
    counter.inc(3)
    Dashboard.get_or_create("TABLE_ADD[t]").record(1.0)

    assert "p99" in Dashboard.watch("SERVE_TTFT[lm]")
    assert "0.750" in Dashboard.watch("SLOT_OCC[lm]")
    assert "total = 3" in Dashboard.watch("SERVE_SHED[lm]")
    assert "count = 1" in Dashboard.watch("TABLE_ADD[t]")
    assert Dashboard.watch("nope") == "[nope] not monitored"


def test_histogram_summary_mean_max():
    h = Histogram("t_mm", window=16, register=False)
    for v in (1.0, 2.0, 3.0, 94.0):
        h.record(v)
    s = h.summary()
    assert s["mean_ms"] == pytest.approx(25.0)
    assert s["max_ms"] == 94.0
    assert "mean = 25.000 ms" in h.info_string()
    assert "max = 94.000 ms" in h.info_string()
    # aging out: max follows the WINDOW, not lifetime
    for _ in range(16):
        h.record(5.0)
    s = h.summary()
    assert s["max_ms"] == 5.0 and s["mean_ms"] == 5.0
    assert s["count"] == 20                       # lifetime count survives


def test_histogram_concurrent_record_vs_percentiles():
    """Ring wrap-around under contention: percentiles taken WHILE other
    threads hammer record() must always come from real recorded values
    (window smaller than the write volume forces constant wrapping)."""
    h = Histogram("t_conc", window=64, register=False)
    stop = threading.Event()
    errors = []

    def writer(ix: int) -> None:
        # every recorded value lives in [1, 2] — any torn read would
        # surface as a percentile outside the band (e.g. the 0.0 of an
        # unwritten slot miscounted as live)
        i = 0
        while not stop.is_set():
            h.record(1.0 + ((ix + i) % 100) / 100.0)
            i += 1

    def reader() -> None:
        while not stop.is_set():
            try:
                qs = h.percentiles((0, 50, 99, 100))
                s = h.summary()
            except Exception as exc:      # pragma: no cover
                errors.append(exc)
                return
            if h.count:                    # after the first record landed
                for v in list(qs.values()) + [s["mean_ms"], s["max_ms"]]:
                    if not 1.0 <= v <= 2.0:
                        errors.append(AssertionError(f"torn value {v}"))
                        return

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors[0]
    assert h.count > 64                    # the ring wrapped many times
    assert len(h.percentiles((50,))) == 1  # still functional after


def test_counter_monotonic():
    c = Counter("t_ctr", register=False)
    c.inc()
    c.inc(9)
    assert c.get() == 10
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.get() == 10


# -- export surface ----------------------------------------------------------

def _populate_dashboard():
    Dashboard.reset()
    h = Dashboard.get_or_create_histogram("SERVE_TTFT[lm]")
    for v in (1.5, 2.5, 300.0):
        h.record(v)
    Dashboard.get_or_create_gauge("DECODE_TPS[lm]").set(123.5)
    Dashboard.get_or_create_counter("SERVE_SHED[lm]").inc(7)
    m = Dashboard.get_or_create("TABLE_ADD[t]")
    m.record(4.25)
    m.record(1.75)


def test_snapshot_covers_every_instrument():
    _populate_dashboard()
    snap = Dashboard.snapshot()
    assert snap["SERVE_TTFT[lm]"]["type"] == "histogram"
    assert snap["SERVE_TTFT[lm]"]["count"] == 3
    assert snap["SERVE_TTFT[lm]"]["max_ms"] == 300.0
    assert snap["DECODE_TPS[lm]"] == {"type": "gauge", "value": 123.5}
    assert snap["SERVE_SHED[lm]"] == {"type": "counter", "value": 7}
    assert snap["TABLE_ADD[t]"]["count"] == 2
    assert snap["TABLE_ADD[t]"]["avg_ms"] == pytest.approx(3.0)
    assert json.loads(json.dumps(snap)) == snap       # plain data only


def test_snapshot_roundtrips_jsonl_and_prometheus():
    """The acceptance-criteria identity: one snapshot, three sinks, same
    values."""
    _populate_dashboard()
    sink = io.StringIO()
    exporter = MetricsExporter(interval_s=60.0, sink=sink)
    record = exporter.report_once()
    snap = record["snapshot"]

    # JSON-lines: the archived line deserializes to the identical snapshot
    line = sink.getvalue().strip().splitlines()[0]
    assert json.loads(line)["snapshot"] == snap

    # Prometheus text: every (instrument, stat) sample carries EXACTLY
    # the snapshot's value (repr round-trip, not approx). The expected
    # sample names follow the renderer's naming rule.
    text = exporter.prometheus()
    assert text == render_prometheus(snap)
    parsed = parse_prometheus(text)
    import re as _re
    for name, row in snap.items():
        base = _re.sub(r"[^a-zA-Z0-9_]", "_",
                       name.partition("[")[0].lower()).strip("_")
        expected = {}
        for field, value in row.items():
            if field == "type":
                continue
            full = f"mv_{base}" if field == "value" else f"mv_{base}_{field}"
            expected[full] = float(value)
        assert parsed[name] == expected


def test_exporter_interval_deltas():
    _populate_dashboard()
    exporter = MetricsExporter(interval_s=60.0)
    exporter.report_once()
    Dashboard.get_or_create_counter("SERVE_SHED[lm]").inc(5)
    Dashboard.get_or_create_histogram("SERVE_TTFT[lm]").record(9.0)
    time.sleep(0.02)
    rec = exporter.report_once()
    assert rec["interval_s"] > 0
    d = rec["deltas"]
    assert d["SERVE_SHED[lm]"]["value"] == 5
    assert d["SERVE_SHED[lm]"]["value_per_s"] > 0
    assert d["SERVE_TTFT[lm]"]["count"] == 1
    # gauges have no monotone fields -> never in deltas
    assert "DECODE_TPS[lm]" not in d
    # a reset instrument reports no (negative) delta
    Dashboard.get_or_create_histogram("SERVE_TTFT[lm]").reset()
    rec = exporter.report_once()
    assert "SERVE_TTFT[lm]" not in rec["deltas"]


def test_exporter_thread_writes_lines(tmp_path):
    _populate_dashboard()
    path = str(tmp_path / "metrics.jsonl")
    exporter = MetricsExporter(interval_s=0.05, sink=path).start()
    _wait(lambda: exporter.reports >= 2)
    exporter.stop(final_report=True)
    lines = open(path).read().strip().splitlines()
    assert len(lines) >= 3
    for line in lines:
        rec = json.loads(line)
        assert "SERVE_TTFT[lm]" in rec["snapshot"]


def test_exporter_snapshots_outside_its_own_lock():
    """Regression (locklint LK204, found by this PR's lint pass):
    report_once used to call Dashboard.snapshot() — the registry lock
    plus every instrument's — while holding the exporter's private lock,
    serializing concurrent prometheus() scrapes and stop() behind the
    whole sweep. The runtime witness proves the fix structurally: after
    reports, no (exporter-lock -> registry-lock) order edge may exist."""
    from multiverso_tpu.analysis import lockwatch

    _populate_dashboard()
    exporter = MetricsExporter(interval_s=60.0)
    exporter.report_once()
    exporter.report_once()
    assert ("dashboard.MetricsExporter._lock",
            "dashboard.Dashboard._lock") not in lockwatch.edges()


def test_exporter_reports_commit_in_snapshot_order(monkeypatch):
    """Regression for the LK204 fix's new race: with the snapshot taken
    outside the exporter's state lock, two concurrent report_once calls
    (the reporter loop racing stop()'s final report) could commit out of
    snapshot order — the older snapshot landing as newest double-counts
    the interval its deltas re-span. _report_lock serializes the
    snapshot+commit pair WITHOUT re-serializing prometheus() scrapes
    behind the registry sweep; intervals run on the monotonic clock so
    a wall-clock step (NTP) can't skew the rates either."""
    import time as _time

    _populate_dashboard()
    exporter = MetricsExporter(interval_s=60.0)
    exporter.report_once()
    # a backwards WALL clock step must not produce a negative interval
    real_time = _time.time
    monkeypatch.setattr(time, "time", lambda: real_time() - 30.0)
    rec = exporter.report_once()
    monkeypatch.undo()
    assert rec["interval_s"] >= 0
    # wedge one report mid-sweep: a concurrent report must WAIT (commit
    # order == snapshot order), while a scrape must NOT
    entered, release = threading.Event(), threading.Event()
    real_snapshot = Dashboard.snapshot

    def slow_snapshot():
        snap = real_snapshot()
        entered.set()
        release.wait(10)
        return snap

    monkeypatch.setattr(Dashboard, "snapshot", staticmethod(slow_snapshot))
    t = threading.Thread(target=exporter.report_once)
    t.start()
    second_done = threading.Event()
    t2 = threading.Thread(
        target=lambda: (exporter.report_once(), second_done.set()))
    try:
        assert entered.wait(5)
        t2.start()
        assert not second_done.wait(0.3), \
            "concurrent report_once overtook a mid-snapshot one"
        exporter.prometheus()           # scrape stays unblocked
        release.set()
        assert second_done.wait(5)
    finally:
        release.set()
        t.join(10)
        t2.join(10)
    assert exporter.reports == 4
    rec = exporter.report_once()
    assert rec["interval_s"] is not None and rec["interval_s"] >= 0


def test_dashboard_reset_detaches_running_exporter(tmp_path):
    """The test-isolation contract: Dashboard.reset() must stop any
    still-running reporter thread — a leaked exporter would keep
    snapshotting (and writing its sink) across every later test."""
    Dashboard.reset()
    exporter = MetricsExporter(interval_s=0.05,
                               sink=str(tmp_path / "m.jsonl")).start()
    _wait(lambda: exporter.reports >= 1)
    thread = exporter._thread
    assert thread is not None and thread.is_alive()
    Dashboard.reset()
    assert exporter._thread is None
    assert not thread.is_alive()
    assert Dashboard._reporters == []
    exporter.stop()                               # idempotent


def test_slo_windowed_burn_status():
    """Rolling-window SLO: value vs target, breach fraction, and burn
    (breach over error budget) — all riding snapshot() as plain data."""
    Dashboard.reset()
    hist = Dashboard.get_or_create_histogram("SERVE_TTFT[lm]")
    slo = Dashboard.set_slo("SERVE_TTFT[lm]", 100.0, percentile=90.0)
    for _ in range(10):
        hist.record(10.0)
    s = slo.summary()
    assert s["ok"] == 1 and s["breach_frac"] == 0.0 and s["burn"] == 0.0
    for _ in range(10):
        hist.record(500.0)
    s = slo.summary()
    assert s["ok"] == 0 and s["value_ms"] == 500.0
    assert s["breach_frac"] == pytest.approx(0.5)
    assert s["burn"] == pytest.approx(5.0)        # 50% breach / 10% budget
    snap = Dashboard.snapshot()
    row = snap["SLO_P90[SERVE_TTFT[lm]]"]
    assert row["type"] == "slo" and row["ok"] == 0
    assert json.loads(json.dumps(snap)) == snap   # still plain data
    assert "BURNING" in Dashboard.watch("SLO_P90[SERVE_TTFT[lm]]")
    # set_slo on the same (source, percentile) re-targets in place
    assert Dashboard.set_slo("SERVE_TTFT[lm]", 1000.0,
                             percentile=90.0) is slo
    assert slo.summary()["ok"] == 1
    # rolling: the breaching samples age out of the window
    for _ in range(Histogram.WINDOW):
        hist.record(1.0)
    assert slo.summary()["breach_frac"] == 0.0


# -- traced serving ----------------------------------------------------------

def test_batcher_handoff_keeps_trace_ids(mv_session, traced):
    """Trace-context propagation across the batcher worker-thread
    boundary: each request's queue-wait/exec spans carry ITS trace id
    (no cross-request leakage), even co-batched in one flush."""
    from multiverso_tpu.serving import InferenceServer

    class Echo:
        source = (lambda: (None, 0), lambda: 0)

        def run(self, payloads, bucket, snap):
            return [p for p in payloads]

    srv = InferenceServer("t")
    srv.register("echo", Echo(), max_batch=8, deadline_ms=5.0)
    futs = [srv.submit("echo", i) for i in range(4)]
    for f in futs:
        f.result(timeout=10)
    _wait(lambda: sum(s.name == "serve.request"
                      for s in traced.spans()) == 4)
    spans = traced.spans()
    roots = [s for s in spans if s.name == "serve.request"]
    assert len({r.trace_id for r in roots}) == 4    # one trace per request
    for root in roots:
        children = [s for s in spans if s.trace_id == root.trace_id
                    and s is not root]
        names = {s.name for s in children}
        assert {"queue.wait", "batch.exec"} <= names
        for s in children:
            assert s.parent_id == root.span_id      # no leaked parents
    # flush-thread spans carry the bucket decision
    execs = [s for s in spans if s.name == "batch.exec"]
    assert all(s.attrs["bucket"] == 4 for s in execs)
    assert all(s.attrs["batch_n"] == 4 for s in execs)


def test_traced_decode_request_end_to_end(mv_session, traced, tmp_path):
    """CI smoke (the ISSUE acceptance walk): a tiny traced serving
    request through the continuous-batching engine -> Chrome trace JSON
    -> structural validation (monotonic ts, matched B/E, ONE root per
    request) -> the root's trace contains queue wait, admission/prefill
    and >=1 decode iteration under the same trace id."""
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    srv.register_decoder("lm", lm, slots=4, max_prompt=8, max_new=6)

    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(2, 8, dtype=np.int32)]
    futs = [srv.submit("lm", {"prompt": p, "max_new": 4}) for p in prompts]
    replies = [f.result(timeout=60) for f in futs]
    assert all(len(r["result"]) == 4 for r in replies)
    _wait(lambda: sum(s.name == "serve.request"
                      for s in traced.spans()) == 2)

    path = str(tmp_path / "serve_trace.json")
    doc = trace.export_chrome(path)
    events = json.load(open(path))["traceEvents"]
    assert events == doc["traceEvents"]
    stats = trace.validate_chrome_events(events, root_name="serve.request")
    assert stats["roots"] >= 2

    spans = traced.spans()
    roots = [s for s in spans if s.name == "serve.request"]
    assert len(roots) == 2
    assert len({r.trace_id for r in roots}) == 2
    for root in roots:
        tree = [s for s in spans if s.trace_id == root.trace_id]
        names = [s.name for s in tree]
        assert "queue.wait" in names
        admits = [s for s in tree if s.name == "decode.admit"]
        assert len(admits) == 1
        # admission explains itself: slot, its schedule (chunk count +
        # budget for the default chunked admission), the paged-KV
        # reservation (blocks held + pool free at admit) and the pinned
        # snapshot version — which must match the reply's
        a = admits[0].attrs
        assert {"slot", "chunks", "budget", "blocks", "pool_free",
                "snapshot_version", "prompt_len"} <= set(a)
        assert a["blocks"] >= 1
        # every chunk of the admission is its own span under the same
        # trace, and their count is what the admit span claims
        chunks = [s for s in tree if s.name == "decode.prefill_chunk"]
        assert len(chunks) == a["chunks"] >= 1
        assert all(s.parent_id == root.span_id for s in chunks)
        assert all(s.attrs["budget"] == a["budget"] for s in chunks)
        iters = [s for s in tree if s.name == "decode.iter"]
        assert len(iters) >= 1                    # max_new=4 -> 3 iters
        assert all(s.parent_id == root.span_id for s in iters)
        # children lie inside the root's interval (the nesting the
        # Chrome B/E validation relies on)
        for s in tree:
            assert s.t0 >= root.t0 - 1e-6
            assert s.t1 <= root.t1 + 1e-6
    reply_versions = {r["snapshot_version"] for r in replies}
    admit_versions = {s.attrs["snapshot_version"] for s in spans
                      if s.name == "decode.admit"}
    assert admit_versions == reply_versions


def test_tracing_disabled_no_decode_hot_loop_overhead(mv_session,
                                                      monkeypatch):
    """The overhead guard: with the collector OFF (the default), a full
    generation through the engine must not construct one Span, record
    one event, or touch the collector — the hot loop's only tracing
    cost is the ``enabled()`` attribute read. The same holds for the
    loop's profiler phases: with no ``jax.profiler`` session running no
    ``TraceAnnotation`` is built, and the always-on phase clock under
    the same sites builds no object a pass: its ten contexts are made
    with the engine and entered again and again."""
    import jax

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    assert not trace.enabled()
    assert trace.phase("engine.step") is trace.NULL_SPAN
    calls = {"span": 0, "record": 0, "annotation": 0, "phase": 0}
    real_span_init = trace.Span.__init__

    def counting_init(self, *a, **kw):
        calls["span"] += 1
        return real_span_init(self, *a, **kw)

    real_record = trace.TraceCollector.record

    def counting_record(self, sp):
        calls["record"] += 1
        return real_record(self, sp)

    real_annotation_init = jax.profiler.TraceAnnotation.__init__

    def counting_annotation(self, name, *a, **kw):
        # jax annotates its own compiles; the program's carry the prefix
        calls["annotation"] += name.startswith(trace.PROFILER_PREFIX)
        return real_annotation_init(self, name, *a, **kw)

    monkeypatch.setattr(jax.profiler.TraceAnnotation, "__init__",
                        counting_annotation)
    monkeypatch.setattr(trace.Span, "__init__", counting_init)
    real_phase_init = trace._Phase.__init__

    def counting_phase(self, *a, **kw):
        calls["phase"] += 1
        return real_phase_init(self, *a, **kw)

    monkeypatch.setattr(trace._Phase, "__init__", counting_phase)
    monkeypatch.setattr(trace.TraceCollector, "record", counting_record)

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=8)
    assert calls["phase"] == 10                  # made with the engine
    calls["phase"] = 0
    out = srv.submit("lm", np.arange(1, 6, dtype=np.int32)).result(
        timeout=60)
    assert len(out["result"]) == 8               # 7 decode iterations ran
    assert calls == {"span": 0, "record": 0, "annotation": 0, "phase": 0}
    # the clock ran (every pass entered its phases) and built nothing
    assert engine.stats()["phase_ms"]["engine.iter"]["n"] >= 8
    assert trace.collector().spans() == []
    # the ALWAYS-ON flight recorder was live the whole time — proving
    # the zero-Span guarantee holds with black-box recording running —
    # and it added no compiled trace to the fused step
    assert engine.recorder is not None and engine.recorder.total > 0
    assert engine.step_cache_size() == 1


def test_tail_sampled_decode_keeps_only_sampled_trees(mv_session):
    """Serving-path tail sampling: with an unreachable SLO and no head
    sample, a healthy engine's requests leave NOTHING in the ring (the
    leave-it-on posture); with head_n=1 every tree survives intact."""
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=4)
    try:
        trace.enable(4096, tail=trace.TailConfig(slo_ms=1e9, head_n=0))
        for _ in range(2):
            srv.submit("lm", np.arange(1, 5, dtype=np.int32)).result(
                timeout=60)
        # snapshot.pin spans are roots of their own traces, so completed
        # counts >= the two requests — but NOTHING may survive the
        # sampler (no breach, no error, no head sample)
        _wait(lambda: trace.collector().tail_completed >= 2)
        col = trace.collector()
        assert col.spans() == []                 # every tree discarded
        assert col.tail_kept == 0
        assert col.tail_discarded == col.tail_completed >= 2

        trace.enable(4096, tail=trace.TailConfig(slo_ms=1e9, head_n=1))
        srv.submit("lm", np.arange(1, 5, dtype=np.int32)).result(
            timeout=60)
        _wait(lambda: any(s.name == "serve.request"
                          for s in trace.collector().spans()))
        spans = trace.collector().spans()
        req_ids = {s.trace_id for s in spans if s.name == "serve.request"}
        assert len(req_ids) == 1
        tree = [s for s in spans if s.trace_id in req_ids]
        names = {s.name for s in tree}
        # the whole tree survived the sampler, parentage intact
        assert {"serve.request", "queue.wait", "decode.admit",
                "decode.iter"} <= names
        root = [s for s in tree if s.name == "serve.request"][0]
        assert root.attrs["tail_keep"] == "head"
        assert all(s.parent_id == root.span_id for s in tree
                   if s is not root)
        # tracing adds no compiled trace, and a clean traced run trips
        # no watchdog while the flight recorder runs throughout
        stats = engine.stats()
        assert stats["step_traces"] == stats["prefill_traces"] == 1
        assert stats["decode_step_retraces"] == 0
        assert stats["watchdog_trips"] == 0
        assert stats["flight_records"] > 0
    finally:
        trace.disable()
        trace.collector().clear()


def test_table_add_span_tagged(mv_session, traced):
    """TABLE_ADD's trace twin carries the table name and the version the
    apply produced — the join key between a serving trace's
    snapshot_version and the training write that created it."""
    table = mv_session.create_table("array", 8, name="obs_t")
    table.add(np.ones(8, np.float32))
    table.add(np.ones(8, np.float32))
    adds = [s for s in traced.spans() if s.name == "table.add"]
    assert len(adds) == 2
    assert [s.attrs["version"] for s in adds] == [1, 2]
    assert all(s.attrs["table"] == "obs_t" for s in adds)
