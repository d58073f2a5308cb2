"""Scaling-harness floors (VERDICT r2 item 2 / r3 items 1-2).

Two guards:

* the REAL-shape sweep (the docs/DISTRIBUTED.md methodology: batch
  2048/device, vocab 20k, 25-batch dispatches) must clear an eff_norm
  floor at dp=8 — this is the round-4 headline claim (the dispatch-mode
  delta exchange lifted it from 0.43 to ~0.7; the floor holds margin for
  host noise). A regression here means the dp data plane re-grew
  per-batch table collectives or the exchange got more expensive.
* the quick-shape sweep stays sane (finite, positive, dp=1 == 1.0) and
  its >1 artifacts are ANNOTATED, not clamped (`saturated` flag) — the
  honesty contract for MULTICHIP_r*.json.

The virtual CPU devices timeshare ``os.cpu_count()`` cores; eff_norm
charges the timesharing to the machine and leaves sharding/collective/
exchange overhead — the thing the framework controls — in the
measurement. On real chips the same sweep must clear the BASELINE.json
bar (>= 0.9 at 8->64).
"""

import os

import numpy as np
import pytest


def _dp1_contended(baseline_ms: float, band: float = 0.05) -> bool:
    """Contention sentinel: re-measure the dp=1 baseline twice; spread
    beyond a ±5% run-to-run band means the host is contended RIGHT NOW
    and an eff_norm miss is environmental, not a data-plane regression."""
    from tools.scaling_bench import w2v_weak_scaling

    # repeats=2 matches dryrun_sweep's best-of-2 estimator — single-shot
    # re-measurements are systematically slower than a best-of-2 and
    # would inflate spread, mis-classifying real regressions as noise
    times = [baseline_ms] + [
        w2v_weak_scaling([1], per_dev_batch=2048, vocab=20000, dim=128,
                         steps=25, repeats=2)[0]["time_ms"]
        for _ in range(2)]
    return (max(times) - min(times)) / min(times) > band


def test_w2v_real_shape_efficiency_floor():
    from tools.scaling_bench import dryrun_sweep

    # r5 floor, tightened to the measured band: the dispatch exchange
    # measures eff_norm 0.96-0.97 at dp=8 on an idle host (overhead ~3%,
    # MULTICHIP_r04); 0.85 holds ~11 points of margin for host noise
    # while still failing a reintroduction of the r3 per-batch
    # dense-allreduce path (which measured 0.43).
    # A miss only COUNTS on a quiet host: the sentinel re-measures the
    # dp=1 baseline and retries/skips when its spread exceeds the noise
    # band, so the floor can't intermittently fail for environmental
    # reasons and train people to rerun red CI.
    rows = None
    for attempt in range(3):
        rows = dryrun_sweep([1, 8])
        by_dp = {r["dp"]: r for r in rows}
        assert by_dp[1]["eff_norm"] == 1.0
        for r in rows:
            assert np.isfinite(r["pairs_per_sec"]) and r["pairs_per_sec"] > 0
        floor_ok = by_dp[8]["eff_norm"] >= 0.85
        # bench-band guard on the sweep's own overhead accounting (the
        # number MULTICHIP_r*.json embeds): dispatch exchange measures
        # ~3%; 10% is the band edge (VERDICT r4 item 5)
        band_ok = by_dp[8]["overhead_frac"] <= 0.10
        if floor_ok and band_ok:
            return
        if not _dp1_contended(by_dp[1]["time_ms"]):
            # quiet host: the miss is attributable — a real regression
            assert floor_ok, rows
            assert band_ok, rows
    pytest.skip("host contended (dp=1 spread beyond the ±5% noise band "
                f"on every attempt); eff_norm floor not attributable: {rows}")


def test_quick_sweep_sane_and_saturation_annotated():
    from tools.scaling_bench import quick_sweep

    rows = quick_sweep([1, 8])
    by_dp = {r["dp"]: r for r in rows}
    assert by_dp[1]["eff_norm"] == 1.0 and not by_dp[1]["saturated"]
    for r in rows:
        assert np.isfinite(r["pairs_per_sec"]) and r["pairs_per_sec"] > 0
        # eff_raw is a ratio of two CPU timings (over 1 whenever the
        # dp=1 leg was the one a neighbour slowed): finite and positive
        # is all a shared host can promise
        assert np.isfinite(r["eff_raw"]) and r["eff_raw"] > 0
        # the annotation contract: > 1 values carry the saturated flag
        assert r["saturated"] == (r["eff_norm"] > 1.0 + 1e-9)


def test_collective_sweep_bandwidths_sane():
    from tools.scaling_bench import collective_sweep

    rows = collective_sweep([1, 8], payload_mb=1.0, repeats=3, inner=4)
    assert {(r["op"], r["dp"]) for r in rows} == {
        ("psum", 1), ("psum", 8), ("all_gather", 1), ("all_gather", 8)}
    for r in rows:
        assert r["time_ms"] > 0 and np.isfinite(r["algbw_gbps"])
        assert r["algbw_gbps"] > 0
