"""Flight recorder (serving/flight_recorder.py) + tools/engine_timeline.py.

Pure host-side units for the ring, its summaries and exports, then the
engine integration: the always-on recorder rides the decode loop
without adding a compiled trace, and its records join the engine's
public progress surface (``iters_total`` / ``ENGINE_ITERS``).
"""

import json
import time

import numpy as np
import pytest

from multiverso_tpu import trace
from multiverso_tpu.serving.flight_recorder import FIELDS, FlightRecorder
from tools.engine_timeline import load_ring, main, render, timeline_report


def _rec(it, ts, busy=1.0, step=0.5, live=1, reserved=0, queue=0,
         queue_age=0.0, prefill=0, decode=1, pool_free=-1, pool_live=-1,
         pool_shared=-1, version=0, admitted=(), completed=(),
         spec_proposed=-1, spec_accepted=-1, kv_quant=-1,
         quant_scale_blocks=-1, kv_block_s=-1.0, tenants_live=-1,
         sp_chunks=-1, kv_live_block_share=-1.0, chunks_behind_step=0,
         steps_ahead=0, phases=(), gap_ms=0.0):
    return (it, ts, busy, step, live, reserved, queue, queue_age,
            prefill, decode, pool_free, pool_live, pool_shared, version,
            admitted, completed, spec_proposed, spec_accepted, kv_quant,
            quant_scale_blocks, kv_block_s, tenants_live, sp_chunks,
            kv_live_block_share, chunks_behind_step, steps_ahead, phases,
            gap_ms)


# -- ring ---------------------------------------------------------------------

def test_ring_wrap_preserves_newest_records():
    fr = FlightRecorder(capacity=4, name="t")
    for i in range(10):
        fr.record(_rec(i + 1, i * 0.01))
    recs = fr.records()
    assert [r["it"] for r in recs] == [7, 8, 9, 10]     # newest survive
    assert list(recs[0]) == list(FIELDS)
    assert fr.total == 10
    s = fr.summary()
    assert s["wrapped"] and s["retained"] == 4 and s["iterations"] == 10


def test_summary_utilization_and_token_split():
    fr = FlightRecorder(capacity=64, name="t")
    # 10 iterations 10 ms apart, 5 ms busy each -> ~50% busy, ~5 ms gaps
    for i in range(10):
        fr.record(_rec(i + 1, 1000.0 + i * 0.010, busy=5.0, step=4.0,
                       prefill=(8 if i < 2 else 0), decode=2))
    s = fr.summary()
    assert 0.40 < s["busy_frac"] < 0.65
    assert s["busy_frac"] + s["idle_frac"] == pytest.approx(1.0)
    assert s["prefill_tokens"] == 16 and s["decode_tokens"] == 20
    assert s["prefill_share"] == pytest.approx(16 / 36)
    assert s["steps"] == 10
    assert s["mean_step_ms"] == pytest.approx(4.0)
    assert 4.0 < s["max_idle_gap_ms"] < 6.5


def test_empty_ring_summary_is_zeroed():
    s = FlightRecorder(capacity=8, name="t").summary()
    assert s["iterations"] == 0 and s["idle_frac"] == 0.0
    assert not s["wrapped"]


# -- exports ------------------------------------------------------------------

def test_jsonl_dump_roundtrips_through_engine_timeline(tmp_path):
    fr = FlightRecorder(capacity=64, name="eng")
    for i in range(20):
        fr.record(_rec(i + 1, i * 0.010, busy=5.0, step=4.0, live=2,
                       queue=1, queue_age=3.0,
                       prefill=(16 if i < 5 else 0), decode=2,
                       admitted=(i + 1,) if i < 5 else ()))
    path = str(tmp_path / "ring.jsonl")
    assert fr.export_jsonl(path) == 20
    meta, records = load_ring(path)
    assert meta["name"] == "eng" and meta["fields"] == list(FIELDS)
    assert len(records) == 20
    assert records[0]["admitted"] == [1]          # JSON tuples -> lists

    report = timeline_report(records, buckets=4)
    assert report["iterations"] == 20
    assert report["prefill_tokens"] == 80 and report["decode_tokens"] == 40
    assert report["peak_live"] == 2
    assert len(report["buckets"]) == 4
    # the admission wave's prefill concentrates in the opening bucket
    assert report["buckets"][0]["prefill_toks"] == 80
    assert report["buckets"][-1]["prefill_toks"] == 0
    assert 0.3 < report["busy_frac"] < 0.7
    text = render(report, meta["name"])
    assert "eng" in text and "utilization" in text and "bubbles" in text

    # the CLI walks the same path (exit 0 on a well-formed dump)
    assert main([path, "--buckets", "4"]) == 0
    assert main([str(tmp_path / "missing.jsonl")]) == 2


def test_chrome_counter_tracks_merge_with_span_export():
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), pool_free=3, pool_live=1))
    counters = fr.chrome_counter_events()
    assert all(e["ph"] == "C" for e in counters)
    assert {e["name"] for e in counters} == {
        "fr/eng/slots", "fr/eng/queue", "fr/eng/tokens",
        "fr/eng/kv_blocks"}
    trace.enable(64)
    try:
        with trace.span("serve.request", root=True, model="m"):
            pass
        doc = trace.export_chrome()
    finally:
        trace.disable()
        trace.collector().clear()
    merged = fr.merge_chrome(doc)
    # counter events ride along WITHOUT breaking the B/E structural
    # contract (the validator skips non-B/E phases by design)
    trace.validate_chrome_events(merged["traceEvents"],
                                 root_name="serve.request")
    assert sum(e["ph"] == "C" for e in merged["traceEvents"]) == 4
    assert [e["ts"] for e in merged["traceEvents"]] == sorted(
        e["ts"] for e in merged["traceEvents"])


def test_spec_counter_track_and_legacy_tuple_tolerance():
    """The spec columns ride the END of FIELDS: spec engines get a
    ``fr/<name>/spec`` counter track, -1 columns (spec_k=0) emit none,
    and a pre-PR-11 16-field tuple still reads cleanly everywhere
    (records/summary/chrome skip the absent tail columns)."""
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), spec_proposed=4, spec_accepted=3))
    events = fr.chrome_counter_events()
    spec = [e for e in events if e["name"] == "fr/eng/spec"]
    assert len(spec) == 1
    assert spec[0]["args"] == {"proposed": 4, "accepted": 3}
    assert fr.records()[0]["spec_proposed"] == 4

    off = FlightRecorder(capacity=8, name="off")
    off.record(_rec(1, time.monotonic()))
    assert not any(e["name"].endswith("/spec")
                   for e in off.chrome_counter_events())

    legacy = FlightRecorder(capacity=8, name="old")
    legacy.record(_rec(1, time.monotonic())[:16])   # pre-PR-11 shape
    recs = legacy.records()
    assert len(recs) == 1 and "spec_proposed" not in recs[0]
    assert legacy.summary()["iterations"] == 1
    assert not any(e["name"].endswith("/spec")
                   for e in legacy.chrome_counter_events())

    # pre-quant 18-field tuples (this PR appended kv_quant /
    # quant_scale_blocks at the END) read cleanly the same way
    pre_quant = FlightRecorder(capacity=8, name="pq")
    pre_quant.record(_rec(1, time.monotonic(),
                          spec_proposed=4, spec_accepted=3)[:18])
    recs = pre_quant.records()
    assert "kv_quant" not in recs[0] and recs[0]["spec_proposed"] == 4
    assert pre_quant.summary()["iterations"] == 1
    # a quant engine's record carries the columns
    qr = FlightRecorder(capacity=8, name="q")
    qr.record(_rec(1, time.monotonic(), kv_quant=1, quant_scale_blocks=7))
    assert qr.records()[0]["kv_quant"] == 1
    assert qr.records()[0]["quant_scale_blocks"] == 7


def test_tenant_counter_track_and_pre_ledger_tuple_tolerance():
    """The tenant-accounting columns ride the END of FIELDS: cost-ledger
    engines get a ``fr/<name>/tenants`` counter track, -1 columns
    (``-cost_ledger`` off) emit none, and a pre-ledger 20-field tuple
    still reads cleanly everywhere (records/summary/chrome skip the
    absent tail columns — the spec/quant append pattern, continued)."""
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), kv_block_s=0.125, tenants_live=3))
    events = fr.chrome_counter_events()
    tenants = [e for e in events if e["name"] == "fr/eng/tenants"]
    assert len(tenants) == 1
    assert tenants[0]["args"] == {"kv_block_s": 0.125, "live": 3}
    assert fr.records()[0]["kv_block_s"] == 0.125
    assert fr.records()[0]["tenants_live"] == 3

    # a ledger-off engine's -1 columns emit no track
    off = FlightRecorder(capacity=8, name="off")
    off.record(_rec(1, time.monotonic()))
    assert not any(e["name"].endswith("/tenants")
                   for e in off.chrome_counter_events())

    # pre-ledger 20-field tuples (this PR appended kv_block_s /
    # tenants_live at the END) read cleanly the same way
    legacy = FlightRecorder(capacity=8, name="old")
    legacy.record(_rec(1, time.monotonic(),
                       kv_quant=1, quant_scale_blocks=5)[:20])
    recs = legacy.records()
    assert "kv_block_s" not in recs[0] and "tenants_live" not in recs[0]
    assert recs[0]["quant_scale_blocks"] == 5
    assert legacy.summary()["iterations"] == 1
    assert not any(e["name"].endswith("/tenants")
                   for e in legacy.chrome_counter_events())


def test_sp_chunks_column_and_pre_seqpar_tuple_tolerance():
    """The seqpar column rides the END of FIELDS: ``-prefill_sp``
    engines record the iteration's sequence-parallel chunk count,
    sp-off engines carry -1, and a pre-seqpar 22-field tuple still
    reads cleanly everywhere (the spec/quant/ledger append pattern,
    continued)."""
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), sp_chunks=2))
    assert fr.records()[0]["sp_chunks"] == 2
    assert fr.summary()["iterations"] == 1

    # a pre-seqpar 22-field tuple (this PR appended sp_chunks at the
    # END) reads cleanly: records/summary/chrome skip the absent tail
    legacy = FlightRecorder(capacity=8, name="old")
    legacy.record(_rec(1, time.monotonic(), tenants_live=3)[:22])
    recs = legacy.records()
    assert "sp_chunks" not in recs[0] and recs[0]["tenants_live"] == 3
    assert legacy.summary()["iterations"] == 1
    legacy.chrome_counter_events()                 # no positional IndexError


def test_kv_live_block_share_column_and_older_tuple_tolerance():
    """The live-block share rides the END of FIELDS: the engine
    records the share of its ``slots x M`` table entries the pass's step
    had to read, -1 where no step ran, and a
    23-field tuple from before the column still reads cleanly."""
    assert FIELDS[23] == "kv_live_block_share"
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), kv_live_block_share=0.15))
    assert fr.records()[0]["kv_live_block_share"] == 0.15
    assert fr.summary()["iterations"] == 1

    older = FlightRecorder(capacity=8, name="old")
    older.record(_rec(1, time.monotonic(), sp_chunks=2)[:23])
    recs = older.records()
    assert "kv_live_block_share" not in recs[0] and recs[0]["sp_chunks"] == 2
    assert older.summary()["iterations"] == 1
    older.chrome_counter_events()


def test_chunks_behind_step_column_and_older_tuple_tolerance():
    """The overlap flag rides the END of FIELDS: 1 where a pass's
    prefill chunk was dispatched while its step was in flight; a
    24-field tuple from before the column still reads cleanly."""
    assert FIELDS[24] == "chunks_behind_step"
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), prefill=4, chunks_behind_step=1))
    assert fr.records()[0]["chunks_behind_step"] == 1
    assert fr.summary()["iterations"] == 1

    older = FlightRecorder(capacity=8, name="old")
    older.record(_rec(1, time.monotonic(), kv_live_block_share=0.25)[:24])
    recs = older.records()
    assert "chunks_behind_step" not in recs[0]
    assert recs[0]["kv_live_block_share"] == 0.25
    assert older.summary()["iterations"] == 1
    older.chrome_counter_events()


def test_steps_ahead_column_and_older_tuple_tolerance():
    """The run-ahead flag rides the END of FIELDS: 1 where a pass's
    step was dispatched with the step before still unread; a 25-field
    tuple from before the column still reads cleanly."""
    assert FIELDS[25] == "steps_ahead"
    fr = FlightRecorder(capacity=8, name="eng")
    fr.record(_rec(1, time.monotonic(), steps_ahead=1))
    assert fr.records()[0]["steps_ahead"] == 1
    assert fr.summary()["iterations"] == 1

    older = FlightRecorder(capacity=8, name="old")
    older.record(_rec(1, time.monotonic(), chunks_behind_step=1)[:25])
    recs = older.records()
    assert "steps_ahead" not in recs[0]
    assert recs[0]["chunks_behind_step"] == 1
    assert older.summary()["iterations"] == 1
    older.chrome_counter_events()


# -- engine integration -------------------------------------------------------

def test_engine_records_iterations_without_new_traces(mv_session):
    """The acceptance invariant: flight recording is pure host state —
    the fused step still compiles EXACTLY once, iteration progress is
    public (stats/counter), and the ring's admitted/completed ids track
    real requests."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import InferenceServer

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=48)
    srv = InferenceServer("t")
    engine = srv.register_decoder("lm", TransformerLM(cfg), slots=2,
                                  max_prompt=8, max_new=6)
    assert engine.recorder is not None            # always-on by default
    futs = [srv.submit("lm", np.arange(1, 5, dtype=np.int32))
            for _ in range(3)]
    for f in futs:
        assert len(f.result(timeout=60)["result"]) == 6

    # the pass's flight record lands just AFTER the futures resolve:
    # settle until the ring's token accounting catches up with stats
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        stats = engine.stats()
        if (stats["live_seqs"] == 0
                and sum(r["decode_toks"]
                        for r in engine.recorder.records())
                == stats["tokens"]):
            break
        time.sleep(0.01)
    assert stats["step_traces"] == 1              # no new compiled traces
    assert stats["prefill_traces"] == 1
    assert stats["iters_total"] >= 5
    assert stats["flight_records"] == engine.recorder.total > 0
    assert stats["last_iter_age_s"] >= 0.0
    assert Dashboard.get_or_create_counter("ENGINE_ITERS[lm]").get() == \
        stats["iters_total"]

    recs = engine.recorder.records()
    admitted = [rid for r in recs for rid in r["admitted"]]
    completed = [rid for r in recs for rid in r["completed"]]
    assert len(admitted) == len(completed) == 3
    assert set(admitted) == set(completed)
    # paged KV is the default: pool occupancy columns are live
    assert all(r["pool_free"] >= 0 for r in recs)
    assert all(r["version"] >= 0 for r in recs)
    assert sum(r["decode_toks"] for r in recs) == stats["tokens"]
    assert sum(r["prefill_toks"] for r in recs) == 12    # 3 x 4-token
    # ring timestamps are monotonic, busy fits inside the gap walls
    ts = [r["ts"] for r in recs]
    assert ts == sorted(ts)


# -- fleet merge (--merge) ----------------------------------------------------

def _write_dump(path, name, n, t_start, anchor_epoch=None):
    """Hand-written JSONL dump: anchored (epoch rebase) or legacy (no
    anchor fields — the pre-fleet-plane format the merge must tolerate
    per the PR 8/11 old-dump pattern)."""
    meta = {"name": name, "capacity": 64, "total": n, "retained": n,
            "fields": list(FIELDS)}
    if anchor_epoch is not None:
        meta["anchor_epoch_s"] = anchor_epoch
        meta["anchor_mono_s"] = 0.0
    with open(path, "w") as f:
        f.write(json.dumps({"flight_recorder": meta}) + "\n")
        for i in range(n):
            rec = dict(zip(FIELDS, _rec(i + 1, t_start + i * 0.01,
                                        busy=5.0, step=4.0, live=2,
                                        decode=2)))
            f.write(json.dumps(rec) + "\n")


def test_merge_aligns_replicas_on_shared_timebase(tmp_path):
    """tools/engine_timeline.py --merge: two anchored replica dumps
    align by EPOCH time (node1 started 100 ms later, so its busy strip
    starts further right), a legacy no-anchor dump still renders
    (origin-aligned, flagged '~'), and each node's digest row carries
    its own totals."""
    from tools.engine_timeline import merge_report, render_merge

    _write_dump(tmp_path / "r0.jsonl", "node0", 20, 0.0,
                anchor_epoch=1000.0)
    _write_dump(tmp_path / "r1.jsonl", "node1", 10, 0.1,
                anchor_epoch=1000.0)
    _write_dump(tmp_path / "rold.jsonl", "old", 10, 50.0)  # legacy
    dumps = [load_ring(str(tmp_path / p))
             for p in ("r0.jsonl", "r1.jsonl", "rold.jsonl")]
    report = merge_report(dumps, buckets=20)
    assert [n["name"] for n in report["nodes"]] == ["node0", "node1",
                                                    "old"]
    n0, n1, old = report["nodes"]
    assert n0["aligned"] == n1["aligned"] == "epoch"
    assert old["aligned"] == "origin"
    # the shared window opens at node0's first work start (epoch 1000)
    assert report["t0_epoch_s"] == pytest.approx(1000.0 - 0.005)
    # node1 began 100 ms in: its first busy bucket sits right of
    # node0's, and both strips end inside the shared window
    first_busy = [next(i for i, f in enumerate(n["strip"]) if f > 0)
                  for n in (n0, n1)]
    assert first_busy[1] > first_busy[0]
    # the legacy dump origin-aligns: its strip starts at column 0
    # (its own monotonic clock says 50 s, which would otherwise land
    # far outside the window)
    assert old["strip"][0] > 0
    assert n0["decode_tokens"] == 40 and n1["decode_tokens"] == 20
    text = render_merge(report)
    assert "node0 |" in text and "old~|" in text
    assert "3 node(s)" in text


def test_merge_cli(tmp_path):
    _write_dump(tmp_path / "a.jsonl", "a", 5, 0.0, anchor_epoch=10.0)
    _write_dump(tmp_path / "b.jsonl", "b", 5, 0.0, anchor_epoch=10.1)
    assert main(["--merge", str(tmp_path / "a.jsonl"),
                 str(tmp_path / "b.jsonl")]) == 0
    # multiple dumps without --merge is a usage error, loudly
    with pytest.raises(SystemExit):
        main([str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")])
    # single-dump path unchanged
    assert main([str(tmp_path / "a.jsonl"), "--buckets", "4"]) == 0
