"""Render utilization / bubble analysis from a flight-recorder dump.

Input is the JSONL a :class:`serving.flight_recorder.FlightRecorder`
writes (``engine.recorder.export_jsonl(path)`` or a watchdog bundle's
``ring.jsonl``): one meta
line, then one record per engine iteration. This tool answers the
post-hoc capacity questions the ring exists for:

* **where did the wall time go** — busy vs idle fraction over the
  window, the largest idle gaps (bubbles) with their timestamps, and a
  bucketed utilization strip so a ramp/stall is visible at a glance;
* **where did the FLOPs go** — prefill-vs-decode token share, overall
  and per time bucket (a prefill-heavy stripe is an admission wave, a
  decode-only tail is the drain);
* **what was the engine holding** — mean/peak live slots, queue depth
  and max queue age per bucket, pool occupancy when paged;
* **which phase of the loop** — the engine's phase clock rides every
  record (``phases``, ``gap_ms``; docs/OBSERVABILITY.md "Engine
  phases"): the ms a pass under each leaf phase, under none, and
  between passes, and the three slowest passes with their rows, so a
  bubble has a phase or a gap to its name (dumps from before the
  columns render without these lines);
* **was speculation earning its keep** — drafts verified vs accepted
  per bucket as an acceptance-rate strip (spec engines only; pre-PR-11
  dumps and ``spec_k=0`` rings render without it).

Usage::

    python tools/engine_timeline.py RING.jsonl [--buckets 40]
        [--top-gaps 5]
    python tools/engine_timeline.py --merge RING0.jsonl RING1.jsonl ...
        [--buckets 60]

``--merge`` takes one ring dump per replica and renders their
utilization strips ALIGNED on a shared timebase: each dump's monotonic
timestamps rebase to epoch through the anchor its meta line carries
(``anchor_epoch_s``/``anchor_mono_s``), so a stall on node 1 lines up
column-for-column with the admission wave on node 0 that caused it —
the fleet-level "where did the wall go" view the obs plane's collector
feeds on. Dumps predating the anchor fields still render (aligned at
their own window start, flagged ``~`` for approximate) — the PR 8/11
old-dump tolerance pattern.

Pure host-side (no jax): loadable against a dump from any run,
including one scraped out of a dead replica's watchdog bundle.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Tuple

# the wall/busy/gap digest lives in ONE place — flight_recorder.py. That
# module is stdlib-only, but importing it through the package would drag
# jax in, so load the file itself (works against a bare checkout).
_FR_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "multiverso_tpu", "serving", "flight_recorder.py")
_spec = importlib.util.spec_from_file_location("_mv_flight_recorder",
                                               _FR_PATH)
_flight_recorder = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_flight_recorder)
window_digest = _flight_recorder.window_digest


def load_ring(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Parse a flight-recorder JSONL dump -> (meta, records oldest first)."""
    meta: Dict[str, Any] = {}
    records: List[Dict[str, Any]] = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if i == 0 and "flight_recorder" in row:
                meta = row["flight_recorder"]
                continue
            records.append(row)
    return meta, records


def timeline_report(records: List[Dict[str, Any]], buckets: int = 40,
                    top_gaps: int = 5, phases=None) -> Dict[str, Any]:
    """Digest a record list into the report dict ``render`` prints.

    The window opens when the first retained iteration's work began
    (``ts - busy_ms``) and closes at the last record; every bucket
    aggregates the iterations whose record timestamp falls inside it.
    ``phases`` is the meta line's list of the ``phases`` column's names.
    """
    digest = window_digest(records, phases)
    report = {"iterations": len(records), **digest,
              "gaps": digest["gaps"][:top_gaps], "buckets": []}
    report.pop("max_idle_gap_ms")
    # prefix-cache effectiveness over time: the shared-block count rides
    # every record since the prefix-caching PR (-1 in dumps of the
    # contiguous-cache engines that predate PR 30; absent in older dumps
    # — both render as "no cache data")
    report["peak_shared"] = max(
        (r.get("pool_shared", -1) for r in records), default=-1)
    # speculative decoding: drafts verified/accepted ride every record
    # since the spec-decode PR (-1 on spec_k=0 engines; absent in older
    # dumps — both render as "no spec data" and skip the strip)
    spec_prop = sum(max(0, r.get("spec_proposed", -1)) for r in records)
    spec_acc = sum(max(0, r.get("spec_accepted", -1)) for r in records)
    report["spec_enabled"] = any(
        r.get("spec_proposed", -1) >= 0 for r in records)
    report["spec_proposed"] = spec_prop
    report["spec_accepted"] = spec_acc
    report["acceptance_rate"] = (spec_acc / spec_prop if spec_prop
                                 else 0.0)
    if not records:
        return report
    t0 = records[0]["ts"] - records[0]["busy_ms"] / 1e3
    wall = digest["wall_s"]

    n_buckets = max(1, min(int(buckets), len(records)))
    width = wall / n_buckets
    rows: List[Dict[str, Any]] = [
        {"t_s": round(b * width, 6), "iters": 0, "busy_ms": 0.0,
         "prefill_toks": 0, "decode_toks": 0, "live_sum": 0, "live_max": 0,
         "queue_max": 0, "queue_age_ms_max": 0.0, "shared_max": -1,
         "spec_proposed": 0, "spec_accepted": 0}
        for b in range(n_buckets)]
    for r in records:
        b = min(n_buckets - 1, int((r["ts"] - t0) / width))
        row = rows[b]
        row["iters"] += 1
        row["busy_ms"] += r["busy_ms"]
        row["prefill_toks"] += r["prefill_toks"]
        row["decode_toks"] += r["decode_toks"]
        row["live_sum"] += r["live"] + r["reserved"]
        row["live_max"] = max(row["live_max"], r["live"] + r["reserved"])
        row["queue_max"] = max(row["queue_max"], r["queue"])
        row["queue_age_ms_max"] = max(row["queue_age_ms_max"],
                                      r["queue_age_ms"])
        row["shared_max"] = max(row["shared_max"],
                                r.get("pool_shared", -1))
        row["spec_proposed"] += max(0, r.get("spec_proposed", -1))
        row["spec_accepted"] += max(0, r.get("spec_accepted", -1))
    for row in rows:
        row["busy_frac"] = min(1.0, row["busy_ms"] / (width * 1e3))
        row["live_mean"] = (row["live_sum"] / row["iters"]
                            if row["iters"] else 0.0)
        toks = row["prefill_toks"] + row["decode_toks"]
        row["prefill_share"] = row["prefill_toks"] / toks if toks else 0.0
        row["acceptance_rate"] = (row["spec_accepted"]
                                  / row["spec_proposed"]
                                  if row["spec_proposed"] else 0.0)
        del row["live_sum"]
    report["buckets"] = rows
    return report


def merge_report(dumps, buckets: int = 60):
    """Digest N ``(meta, records)`` dumps onto ONE shared timebase.

    Anchored dumps (meta carries ``anchor_epoch_s``/``anchor_mono_s``)
    rebase record timestamps to epoch seconds, so replicas align by
    wall time; un-anchored (old) dumps can't — they align at the shared
    window's origin and are marked ``aligned: "origin"`` so the render
    flags them approximate instead of crashing or silently lying.

    Returns ``{"wall_s", "t0_epoch_s", "nodes": [{"name", "aligned",
    "iterations", "busy_frac", "prefill_tokens", "decode_tokens",
    "peak_live", "strip": [busy_frac per bucket]}]}``.
    """
    rebased = []
    for meta, records in dumps:
        name = meta.get("name", "") or f"engine{len(rebased)}"
        wall = meta.get("anchor_epoch_s")
        mono = meta.get("anchor_mono_s")
        anchored = isinstance(wall, (int, float)) and isinstance(
            mono, (int, float))
        recs = [dict(r) for r in records]
        if anchored:
            for r in recs:
                r["ts"] = wall + (r["ts"] - mono)
        rebased.append((name, anchored, recs))
    # shared window: earliest work start to latest record, over the
    # ANCHORED dumps; origin-aligned dumps shift to start at t0
    starts = [r[0]["ts"] - r[0]["busy_ms"] / 1e3
              for _, anchored, r in rebased if anchored and r]
    t0 = min(starts) if starts else 0.0
    for name, anchored, recs in rebased:
        if not anchored and recs:
            off = t0 - (recs[0]["ts"] - recs[0]["busy_ms"] / 1e3)
            for r in recs:
                r["ts"] += off
    end = max((r[-1]["ts"] for _, _, r in rebased if r), default=t0)
    wall = max(end - t0, 1e-9)
    n_buckets = max(1, int(buckets))
    width = wall / n_buckets
    nodes = []
    for name, anchored, recs in rebased:
        strip = [0.0] * n_buckets
        for r in recs:
            b = min(n_buckets - 1, max(0, int((r["ts"] - t0) / width)))
            strip[b] += r["busy_ms"]
        digest = window_digest(recs)
        nodes.append({
            "name": name,
            "aligned": "epoch" if anchored else "origin",
            "iterations": len(recs),
            "busy_frac": digest["busy_frac"],
            "prefill_tokens": digest["prefill_tokens"],
            "decode_tokens": digest["decode_tokens"],
            "peak_live": digest["peak_live"],
            "strip": [min(1.0, s / (width * 1e3)) for s in strip],
        })
    return {"wall_s": wall, "t0_epoch_s": t0, "buckets": n_buckets,
            "nodes": nodes}


def render_merge(report) -> str:
    """Aligned per-node utilization strips + a per-node summary table."""
    lines = [
        f"fleet timeline: {len(report['nodes'])} node(s) over "
        f"{report['wall_s']:.3f}s shared window "
        f"({report['wall_s'] / report['buckets']:.3f}s per column; "
        f"scale '{_BARS[0]}'=0 .. '{_BARS[-1]}'=1; '~' = old dump, "
        f"origin-aligned)"]
    width = max((len(n["name"]) for n in report["nodes"]), default=4)
    for n in report["nodes"]:
        strip = "".join(_bar(f) for f in n["strip"])
        flag = " " if n["aligned"] == "epoch" else "~"
        lines.append(f"{n['name']:>{width}}{flag}|{strip}|")
    lines.append(f"{'node':>{width}} {'iters':>7} {'busy':>6} "
                 f"{'prefill':>8} {'decode':>8} {'peak':>5}")
    for n in report["nodes"]:
        lines.append(
            f"{n['name']:>{width}} {n['iterations']:>7} "
            f"{n['busy_frac']:>6.1%} {n['prefill_tokens']:>8} "
            f"{n['decode_tokens']:>8} {n['peak_live']:>5}")
    return "\n".join(lines)


_BARS = " .:-=+*#%@"


def _bar(frac: float) -> str:
    """One glyph per bucket, darker = higher."""
    level = min(len(_BARS) - 1, int(frac * (len(_BARS) - 1) + 0.5))
    return _BARS[level]


def render(report: Dict[str, Any], name: str = "") -> str:
    lines: List[str] = []
    lines.append(
        f"engine timeline{f' [{name}]' if name else ''}: "
        f"{report['iterations']} iterations over {report['wall_s']:.3f}s "
        f"— busy {report['busy_frac']:.1%}, idle {report['idle_frac']:.1%}")
    total = report["prefill_tokens"] + report["decode_tokens"]
    lines.append(
        f"tokens: {report['prefill_tokens']} prefill / "
        f"{report['decode_tokens']} decode ({report['prefill_share']:.1%} "
        f"prefill share of {total}); {report['steps']} fused steps, "
        f"mean {report['mean_step_ms']:.3f} ms; peak live "
        f"{report['peak_live']}"
        + (f"; peak shared KV blocks {report['peak_shared']}"
           if report.get("peak_shared", -1) >= 0 else ""))
    if report.get("spec_enabled"):
        lines.append(
            f"speculation: {report['spec_proposed']} drafts verified, "
            f"{report['spec_accepted']} accepted "
            f"({report['acceptance_rate']:.1%} acceptance)")
    if report["gaps"]:
        worst = ", ".join(f"{g['gap_ms']:.1f}ms@{g['t_s']:.3f}s"
                          for g in report["gaps"])
        lines.append(f"largest bubbles: {worst}")
    if report.get("phase_ms"):
        n = max(1, report["iterations"])
        lines.append("phases (ms a pass): " + ", ".join(
            f"{name.replace('engine.', '', 1)} {ms / n:.3f}"
            for name, ms in report["phase_ms"].items()))
        lines.append("slowest passes:")
        for p in report["slowest"]:
            top = max(p["phases"], key=p["phases"].get)
            lines.append(
                f"  it {p['it']} @{p['t_s']:.3f}s busy {p['busy_ms']:.3f}ms "
                f"(gap before {p['gap_ms']:.3f}ms): {top} "
                f"{p['phases'][top]:.3f}ms, under no phase "
                f"{p['busy_ms'] - sum(p['phases'].values()):.3f}ms")
    if report["buckets"]:
        util = "".join(_bar(b["busy_frac"]) for b in report["buckets"])
        pf = "".join(_bar(b["prefill_share"]) for b in report["buckets"])
        lines.append(f"utilization   |{util}|")
        lines.append(f"prefill share |{pf}|   "
                     f"(scale: '{_BARS[0]}'=0 .. '{_BARS[-1]}'=1, "
                     f"{report['wall_s'] / len(report['buckets']):.3f}s "
                     f"per column)")
        has_spec = report.get("spec_enabled", False)
        if has_spec:
            # acceptance over time: a fading strip is the drafter losing
            # the tail (e.g. traffic left its repetitive regime)
            acc = "".join(_bar(b["acceptance_rate"])
                          for b in report["buckets"])
            lines.append(f"acceptance    |{acc}|")
        has_shared = report.get("peak_shared", -1) >= 0
        lines.append(f"{'t_s':>8} {'iters':>6} {'busy':>6} {'live':>6} "
                     f"{'qmax':>5} {'qage_ms':>8} {'prefill':>8} "
                     f"{'decode':>8}"
                     + (f" {'shared':>7}" if has_shared else "")
                     + (f" {'accept':>7}" if has_spec else ""))
        for b in report["buckets"]:
            if not b["iters"]:
                continue
            line = (
                f"{b['t_s']:8.3f} {b['iters']:6d} {b['busy_frac']:6.1%} "
                f"{b['live_mean']:6.2f} {b['queue_max']:5d} "
                f"{b['queue_age_ms_max']:8.1f} {b['prefill_toks']:8d} "
                f"{b['decode_toks']:8d}")
            if has_shared:
                line += f" {max(0, b.get('shared_max', 0)):7d}"
            if has_spec:
                line += f" {b['acceptance_rate']:7.1%}"
            lines.append(line)
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="utilization/bubble report from a flight-recorder dump")
    ap.add_argument("ring", nargs="+",
                    help="flight-recorder JSONL (engine."
                         "recorder.export_jsonl / watchdog bundle "
                         "ring.jsonl); several with --merge")
    ap.add_argument("--merge", action="store_true",
                    help="render the dumps (one per replica) as aligned "
                         "per-node utilization strips on a shared "
                         "timebase")
    ap.add_argument("--buckets", type=int, default=None,
                    help="timeline columns (default 40; 60 with --merge)")
    ap.add_argument("--top-gaps", type=int, default=5,
                    help="largest idle bubbles to list (default 5)")
    args = ap.parse_args(argv)
    if len(args.ring) > 1 and not args.merge:
        ap.error("multiple dumps need --merge")
    buckets = args.buckets if args.buckets is not None else (
        60 if args.merge else 40)
    try:
        dumps = [load_ring(path) for path in args.ring]
    except (OSError, json.JSONDecodeError) as exc:
        print(f"engine_timeline: {exc}", file=sys.stderr)
        return 2
    if args.merge:
        dumps = [(m, r) for m, r in dumps if r]
        if not dumps:
            print("engine_timeline: no dump holds records",
                  file=sys.stderr)
            return 2
        print(render_merge(merge_report(dumps, buckets)))
        return 0
    meta, records = dumps[0]
    if not records:
        print("engine_timeline: dump holds no records", file=sys.stderr)
        return 2
    report = timeline_report(records, buckets, args.top_gaps,
                             meta.get("phases"))
    print(render(report, meta.get("name", "")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
