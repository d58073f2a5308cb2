"""Readings for the limits of ``correct``, on the chip at a cell's own
size (how each limit in ``benchmarks/traffic/*.json`` was set; the
readings themselves are in PERF.md section 2).

    python3 benchmarks/limits.py --workload <cell> --seeds 1,2,3 [--controls 1] [--seconds 6]

For every seed, in one process: the driver's set-up (and, for a served
cell, a short window at the cell's own load), the program's state
dropped, then the sound comparison against the reference (the LOWER
reading) and, with ``--controls 1``, each of the driver's controls and
planted faults compared the same way (the UPPER readings). One JSON line
a seed, nothing judged: the limits are set from these by hand, above the
largest lower reading and below the smallest upper one.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    import multiverso_tpu as mv

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.find(bench["workloads"], args.workload, "workload")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = harness.Ctx(bench, cell, seed, args.seconds, False)
        driver = harness.load_module("drivers", ctx.traffic["driver"])
        mv.init(["limits", "-log_level=error",
                 *ctx.traffic.get("mv_flags", [])])
        try:
            harness.device_facts(ctx, require_tpu=True)
            state = driver.build(ctx, mv)
            if ctx.traffic.get("check_needs_window"):
                driver.window(state, ctx, args.seconds)
            got = driver.release(state, ctx)
            del state
        finally:
            mv.shutdown()
        gc.collect()
        out = {"workload": args.workload, "seed": seed,
               "sound": {r["name"]: r["value"]
                         for r in driver.check(got, ctx)}}
        if args.controls:
            out["upper"] = {
                name: {r["name"]: r["value"] for r in rows}
                for name, rows in driver.controls(got, ctx).items()}
        out["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(out), flush=True)
        del got, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
