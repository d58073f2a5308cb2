"""Diff two serving_bench JSON lines -> regression verdict (exit code).

Its producer is gone: ``tools/serving_bench.py`` (CPU timings of toy
models) was deleted in PR 30, speed is read from ``benchmarks/`` on the
chip, and nothing in the tree writes the JSON lines this tool diffs any
more. It stays only because ``tests/test_bench_compare.py`` stands on
it; ROADMAP.md, Design 5, decides whether it is pointed at
``benchmarks/run.py``'s result lines or deleted with those tests.

It was the perf gate of the CPU serving series: run the bench on the
base and on the candidate, feed both JSON lines here, and the exit code
says whether any tracked metric regressed past its threshold.

Direction is metric-aware: throughput-like metrics (``qps``,
``tokens_per_s``, ``speedup_*``) regress DOWN, latency/overload-like
metrics (``*_ms``, ``shed_rate``) regress UP. Everything else
(``completed``, ``jit_traces``, trace counts, and anything suffixed
``_info`` — the bench-side escape hatch for measured-but-noisy
columns) is informational and never gates. Thresholds are relative: a metric regresses when it
is more than ``--tolerance`` (default 25%, sized for CI-container
noise) worse than the baseline; ``--metric NAME=TOL`` overrides the
tolerance for one metric name (applies wherever that name appears),
and tiny latencies below ``--min-ms`` are ignored (sub-millisecond
percentiles are scheduler noise, not signal).

Usage (``base.json`` / ``new.json``: one such JSON line each)::

    python tools/bench_compare.py base.json new.json [--tolerance 0.25]
        [--metric itl_p99_ms=0.5] [--min-ms 1.0]

Exit status: 0 = no regression, 1 = at least one metric regressed,
2 = inputs malformed/incomparable.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

# metric-name suffix/prefix rules deciding gating direction.
# capacity_seqs / kv_bytes_per_seq are the paged-KV capacity metrics
# (serving_bench's lm_paged_kv A/B): concurrent sequences held at a
# fixed KV-bytes budget regress DOWN, bytes paid per held sequence
# regress UP — the standing gate covers capacity, not just latency.
# watchdog_trips is a HARD gate in practice: a clean bench baseline has
# zero trips, and the zero-baseline rule below makes ANY trip on the
# candidate side regress (worseness = the trip count itself) — a
# watchdog firing during a healthy bench is a bug, not noise.
# lock_order_violations rides the same rule: the runtime witness
# recording a cycle during a clean bench is a latent deadlock.
# prefill_tokens_saved / prefix_hit_rate are the prefix-cache capacity
# metrics (serving_bench's lm_prefix_cache A/B): prompt tokens the
# content-addressed block cache kept off the prefill path, and the
# fraction of looked-up blocks it served — both regress DOWN (a
# candidate that stops hitting the cache re-prefills shared prefixes).
# kv_bytes_per_device is the sharded-decode capacity metric
# (lm_sharded_decode A/B): KV bytes each decode-mesh device must hold —
# tensor parallelism exists to push it DOWN, so it regresses UP.
# decode_step_retraces rides the zero-baseline rule like
# watchdog_trips: the fused step compiles ONCE per engine config, and
# any retrace on the candidate side is the PR 2 ~10x partitioner drag
# sneaking back into the hot loop — a bug, not noise.
# accepted_per_step is the speculative-decoding amortization metric
# (lm_spec_decode A/B): mean EXTRA tokens each fused verify step
# bought — a candidate whose drafter stops matching (or whose verify
# window shrinks) regresses DOWN. acceptance_rate itself archives as
# _info: it depends on the trace's repetitiveness, not on the code.
# dropped_reports (the obs_plane A/B's obs_dropped_reports) rides the
# zero-baseline rule like watchdog_trips: the fleet plane's reports
# are bounded BY DESIGN — a report dropped on an idle loopback
# collector means the bound machinery broke, a bug, not noise.
# requests_lost / output_mismatches are the serving-fleet recovery
# invariants (lm_fleet_chaos A/B): every request accepted by the
# router must resolve, and a replayed request's output must be
# bit-identical to the first completion (deterministic greedy decode)
# — both have a zero baseline by construction, so ANY loss or
# mismatch on the candidate side gates hard. recovery_time_s (death
# flagged -> first re-dispatched completion) regresses UP like a
# latency; fleet_tokens_per_s rides the tokens_per_s rule.
# updates_lost / epoch_fence_rejections_unexpected are the durable
# online-learning invariants (lm_trainer_chaos A/B): every add the
# trainer ACKNOWLEDGED must survive a kill via checkpoint + WAL
# replay, and the epoch fence must reject exactly the staged zombie
# publishes — both zero-baseline hard gates. trainer_recovery_time_s
# (kill -> fleet re-converged on the restarted incarnation) rides the
# recovery_time_s suffix rule; wal_replay_records archives as _info
# (it measures the checkpoint cadence, not the code).
# preempt_output_mismatches / starved_requests are the overload-
# graceful invariants (lm_overload A/B): a preempted-and-resumed
# generation must be bit-identical to its un-preempted oracle, and
# every accepted request must resolve under sustained pressure — both
# zero-baseline hard gates. deadline_drops regresses UP: the A/B's
# deadlines are sized so the priority+preemption leg meets them all
# (zero baseline), so any drop on the candidate side is scheduling
# gone wrong, not traffic. output_mismatches already covers the
# fleet's twin; capacity_seqs covers the optimistic-admission packing
# headline via the existing higher-better rule.
# kv_bytes_moved / xfer_dedup_hit_rate are the disaggregated-serving
# transfer-plane pair (lm_disagg A/B): raw K/V bytes crossing the
# prefill->decode wire regress UP (dedup-on-arrival and chain
# advertisement exist to shrink them), and the fraction of blocks that
# dedup'd instead of shipping regresses DOWN. The saturated tok/s of
# each leg archives as _info — it measures the trace mix, not the code.
# publish_bytes is the mvparam wire's cousin of kv_bytes_moved: bytes a
# publisher shipped per delta stream (post SparseFilter/int8 codec) —
# regressing UP means the wire compression stopped paying. Its ratio
# sibling wire_compressed_ratio archives as *_info (ratio would hit the
# higher-better rule backwards: smaller is better there).
# ttft_long_p50 / itl_short_p99 are the long-context serving pair
# (lm_long_context A/B): the median time-to-first-token of the few
# "document" prompts sequence-parallel prefill exists to speed up, and
# the p99 inter-token latency of the short interactive requests
# decoding while those documents prefill — both regress UP (the gate
# holds the seqpar leg to both: faster documents AND an unstalled
# interactive tail; the off leg's twins archive as *_info).
# accounting_drift is the cost ledger's conservation residual
# (|sum-over-tenants - engine counter| over the integer usage fields,
# serving/accounting.py): the bench archives 0 and the zero-baseline
# rule makes ANY nonzero candidate value gate — attribution that loses
# or invents tokens is corruption, not noise (same contract as
# requests_lost/updates_lost). Per-tenant cost columns archive as
# *_info: they measure the trace's tenant mix, not the code.
_HIGHER_BETTER = ("qps", "tokens_per_s", "speedup", "ratio",
                  "capacity_seqs", "prefill_tokens_saved",
                  "prefix_hit_rate", "accepted_per_step",
                  "xfer_dedup_hit_rate")
_LOWER_BETTER = ("_ms", "shed_rate", "kv_bytes_per_seq",
                 "kv_bytes_per_device", "decode_step_retraces",
                 "watchdog_trips", "lock_order_violations",
                 "dropped_reports", "requests_lost",
                 "output_mismatches", "recovery_time_s",
                 "updates_lost", "epoch_fence_rejections_unexpected",
                 "preempt_output_mismatches", "starved_requests",
                 "deadline_drops", "kv_bytes_moved", "publish_bytes",
                 "accounting_drift", "ttft_long_p50", "itl_short_p99")


def metric_direction(name: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 informational.

    An ``_info`` suffix ALWAYS means informational, overriding the
    pattern rules: benches use it for measured-but-noisy columns (e.g.
    the paged-KV A/B's saturated tok/s and noise-floor latencies) that
    must ride the archive without flapping the standing gate."""
    if name.endswith("_info"):
        return 0
    for pat in _HIGHER_BETTER:
        if name == pat or name.startswith(pat) or name.endswith(pat):
            return 1
    for pat in _LOWER_BETTER:
        if name.endswith(pat) or name == pat:
            return -1
    return 0


def _flatten(prefix: str, node, out: Dict[str, float]) -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        out[prefix] = float(node)


def flatten_workloads(line: dict) -> Dict[str, float]:
    """Dotted metric paths under ``workloads`` (the gated surface; the
    archived ``dashboard`` snapshot is diagnostic, not a gate)."""
    out: Dict[str, float] = {}
    _flatten("", line.get("workloads", {}), out)
    return out


def dropped_gated_metrics(base: dict, new: dict) -> List[str]:
    """Gated-direction metric paths present in ``base`` but ABSENT from
    ``new`` — lost coverage the intersection-only compare would
    otherwise hide (e.g. the ``lm_sharded_decode`` A/B archiving its
    skip marker on a 1-device candidate run while the baseline ran
    under ``--devices``: its zero-baseline ``decode_step_retraces``
    gate would silently vanish). Surfaced as a loud warning, not an
    exit-code flip: metrics legitimately evolve between rounds, but a
    gate disappearing must never be invisible."""
    b, n = flatten_workloads(base), flatten_workloads(new)
    return sorted(path for path in set(b) - set(n)
                  if metric_direction(path.rsplit(".", 1)[-1]) != 0)


def compare(base: dict, new: dict, tolerance: float = 0.25,
            overrides: Dict[str, float] = {}, min_ms: float = 1.0
            ) -> Tuple[List[dict], List[dict]]:
    """Return ``(regressions, rows)``: every compared metric as a row,
    the over-threshold subset as regressions (worst first)."""
    b, n = flatten_workloads(base), flatten_workloads(new)
    rows: List[dict] = []
    regressions: List[dict] = []
    for path in sorted(set(b) & set(n)):
        leaf = path.rsplit(".", 1)[-1]
        sign = metric_direction(leaf)
        if sign == 0:
            continue
        bv, nv = b[path], n[path]
        if sign == -1 and max(bv, nv) < min_ms and leaf.endswith("_ms"):
            continue                      # sub-threshold latency noise
        if bv == 0.0 and sign == 1:
            continue                      # broken baseline: nothing to gate
        # worseness > 0 means NEW is worse, as a fraction of base. A
        # ZERO baseline on a lower-is-better metric (shed_rate 0.0 on a
        # healthy run) must still gate — skipping it would wave through
        # a candidate that starts shedding — so the new value itself
        # stands in as the worseness (0.4 shed_rate > 0.25 tol -> gate;
        # a zero-base *_ms metric past the min-ms floor gates likewise)
        if bv == 0.0:
            worse = nv
        else:
            worse = (bv - nv) / bv if sign == 1 else (nv - bv) / bv
        # most-specific override wins: full dotted path before leaf name
        tol = overrides.get(path, overrides.get(leaf, tolerance))
        row = {"metric": path, "base": bv, "new": nv,
               "worse_frac": round(worse, 4), "tolerance": tol,
               "direction": "up" if sign == 1 else "down",
               "regressed": worse > tol}
        rows.append(row)
        if row["regressed"]:
            regressions.append(row)
    regressions.sort(key=lambda r: r["worse_frac"], reverse=True)
    return regressions, rows


def _load_line(path: str) -> dict:
    """First JSON object found in the file (serving_bench prints ONE
    line, but logs may precede it when stderr was merged)."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    raise ValueError(f"{path}: no JSON object line found")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two serving_bench JSON lines; exit 1 on regression")
    ap.add_argument("base", help="baseline serving_bench JSON line file")
    ap.add_argument("new", help="candidate serving_bench JSON line file")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative worseness gate (default 0.25)")
    ap.add_argument("--metric", action="append", default=[],
                    metavar="NAME=TOL",
                    help="per-metric tolerance override (leaf name or "
                         "full dotted path; repeatable)")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="ignore latency metrics where both sides are "
                         "below this (default 1.0 ms)")
    args = ap.parse_args(argv)
    overrides: Dict[str, float] = {}
    for spec in args.metric:
        name, _, tol = spec.partition("=")
        if not tol:
            ap.error(f"--metric needs NAME=TOL, got {spec!r}")
        overrides[name] = float(tol)
    try:
        base, new = _load_line(args.base), _load_line(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"bench_compare: {exc}", file=sys.stderr)
        return 2
    regressions, rows = compare(base, new, args.tolerance, overrides,
                                args.min_ms)
    if not rows:
        print("bench_compare: no comparable metrics", file=sys.stderr)
        return 2
    dropped = dropped_gated_metrics(base, new)
    if dropped:
        print(f"WARNING: {len(dropped)} gated metric(s) in the baseline "
              f"are ABSENT from the candidate (coverage lost, not "
              f"compared): {', '.join(dropped)}", file=sys.stderr)
    print(f"{len(rows)} metrics compared, {len(regressions)} regressed "
          f"(tolerance {args.tolerance:.0%})")
    print(f"{'metric':<52} {'base':>10} {'new':>10} {'worse':>8}")
    for r in rows:
        flag = " <-- REGRESSED" if r["regressed"] else ""
        print(f"{r['metric']:<52} {r['base']:>10.3f} {r['new']:>10.3f} "
              f"{r['worse_frac']:>+7.1%}{flag}")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
