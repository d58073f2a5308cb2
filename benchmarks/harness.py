"""The harness: one run of one cell, driven by data.

It finds everything by name from ``BENCHMARK.json`` (this file holds no
cell's, configuration's or metric's name): the cell's configuration
file, its traffic file (``benchmarks/traffic/<traffic>.json``, which
names the driver), the driver (``benchmarks/drivers/<driver>.py``), the
plain reference (``benchmarks/reference/<config>.py``) and, for each
per-layer metric, its reader (``benchmarks/readers/<metric up to the
first dot>.py``).

Order of a run: set-up (build, compile, warm up, and the program's own
readings for ``correct``) -> measured window (profiler on when
``--trace 1``) -> peak memory read -> program state dropped -> the
reference, and every number compared beside its limit -> the result.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP = 3


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str) -> types.ModuleType:
    """``benchmarks/<kind>/<name>.py`` by file, so that a name may hold
    ``-`` and ``.`` and a later PR only adds files."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


class Ctx:
    """What a driver, a reference and a reader are handed."""

    def __init__(self, bench, cell, seed, seconds, trace, shrink=None,
                 t0=None):
        self.bench, self.cell = bench, cell
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        cfg_entry = find(bench["configs"], cell["config"], "configuration")
        self.config_name = cell["config"]
        self.config = load_json(ROOT, cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
        self.chips = int(cell["chips"])
        for key, value in (shrink or {}).items():     # selfcheck and tests only
            (self.config if key in self.config else self.traffic)[key] = value
        self.peaks = None           # one row of work.PEAKS, by device_facts()
        self.counters = {}          # what the window counted
        self.tracered = None        # reduced trace, --trace 1 only
        self.window_s = None
        self.t0 = time.perf_counter() if t0 is None else t0   # process start

    def mark(self, label: str) -> None:
        """Seconds since the process started, under ``info.setup_marks``:
        where set-up goes, for PERF.md."""
        self.counters.setdefault("setup_marks", []).append(
            [label, round(time.perf_counter() - self.t0, 3)])

    # a 31-bit seed for programs that take an int32
    @property
    def seed31(self) -> int:
        return self.seed % (2 ** 31 - 1)


def device_facts(ctx: Ctx, require_tpu: bool) -> dict:
    import jax

    from benchmarks import work

    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if require_tpu:
        if facts["platform"] != "tpu":
            print(f"run.py: needs a TPU, found platform "
                  f"{facts['platform']!r}; no result", file=sys.stderr)
            raise SystemExit(NO_CHIP)
        if facts["count"] != ctx.chips:
            print(f"run.py: cell {ctx.cell['name']} is sized for "
                  f"{ctx.chips} chip(s), found {facts['count']}; no result",
                  file=sys.stderr)
            raise SystemExit(NO_CHIP)
        ctx.peaks = work.peaks(facts["kind"])     # unknown kind: an error
    else:
        ctx.peaks = work.SELFCHECK_PEAKS
    return facts


def memory_peak_bytes(program_temp_bytes: int = 0) -> tuple:
    """``(peak on the fullest chip, its parts)``. The allocator's
    ``peak_bytes_in_use`` counts live buffers only: what a running
    program holds for itself (activations, logits) is not in it (read on
    the v5e, PR 24). Where a driver hands over the temporaries of the
    program its window runs (``compiled.memory_analysis()``), the peak is
    the larger of the allocator's and live buffers plus temporaries."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    parts = {"allocator_peak_bytes": int(full.get("peak_bytes_in_use", 0)),
             "live_bytes": int(full.get("bytes_in_use", 0)),
             "program_temp_bytes": int(program_temp_bytes)}
    peak = max(parts["allocator_peak_bytes"],
               parts["live_bytes"] + parts["program_temp_bytes"])
    return peak, parts


def metrics_of(ctx: Ctx, end_to_end: dict, setup_s: float) -> dict:
    """The cell's metrics, by BENCHMARK.json: end to end with
    ``--trace 0``, per layer with ``--trace 1``. A metric with no
    ``workloads`` key belongs to every cell (a per-layer one: to every
    cell that reports the metric it moves)."""
    name = ctx.cell["name"]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in ctx.bench["end_to_end"] if mine(m)]
    out = {}
    if not ctx.trace:
        values = dict(end_to_end, setup_s=setup_s)
        for m in e2e:
            if values.get(m["name"]) is not None:
                out[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        return out
    reported = {m["name"] for m in e2e}
    for m in ctx.bench["per_layer"]:
        if not mine(m) or m["moves"] not in reported:
            continue
        value = load_module("readers", m["name"].split(".")[0]).read(ctx)
        if value is not None:       # nothing to read: left out, never 0
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, shrink: dict | None = None,
             bench: dict | None = None, t0: float | None = None) -> dict:
    """One run of one cell; returns the result line as a dict. ``t0`` is
    the process's start on ``time.perf_counter``; ``shrink`` overrides
    keys of the configuration or traffic file, for ``selfcheck.py`` and
    the tests, which also pass ``require_tpu=False``."""
    t0 = time.perf_counter() if t0 is None else t0
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], cell_name, "workload")
    ctx = Ctx(bench, cell, seed, seconds, trace, shrink, t0)
    driver = load_module("drivers", ctx.traffic["driver"])

    import multiverso_tpu as mv     # absent program: ImportError, exit 1

    from benchmarks import tracered

    ctx.mark("imported")
    mv.init(["bench", "-log_level=error", *ctx.traffic.get("mv_flags", [])])
    try:
        device = device_facts(ctx, require_tpu)
        ctx.mark("device")
        import jax

        # small programs too: every run after the first finds all of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

        state = driver.build(ctx, mv)
        setup_s = time.perf_counter() - t0
        seconds = ctx.seconds
        if trace:                   # a short window of its own (guide 5)
            seconds = min(seconds, float(ctx.traffic.get("trace_seconds", 4)))
        tracer = tracered.Tracer(os.path.join(ROOT, ".bench_trace")) \
            if trace else None
        if tracer:
            tracer.start()
        t_open = time.perf_counter()
        end_to_end = driver.window(state, ctx, seconds)
        ctx.window_s = time.perf_counter() - t_open
        if tracer:
            ctx.tracered = tracer.stop_and_reduce(ctx.window_s)
        device["memory_peak_bytes"], ctx.counters["memory"] = \
            memory_peak_bytes(state.get("program_temp_bytes", 0))
        readings = driver.release(state, ctx)
        del state
    finally:
        mv.shutdown()       # the program is done: its tables and pools go
    gc.collect()
    t_check = time.perf_counter()
    checks = driver.check(readings, ctx)
    ctx.counters["check_s"] = round(time.perf_counter() - t_check, 3)
    if ctx.tracered is not None:
        device["busy_s"] = ctx.tracered["busy_s"]
        device["window_s"] = ctx.tracered["window_s"]
    correct = bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks)
    line = {
        "correct": correct,
        "attempted": int(ctx.counters.get("attempted", 0)),
        "failed": int(ctx.counters.get("failed", 0)),
        "metrics": metrics_of(ctx, end_to_end, setup_s),
        "device": device,
    }
    if ctx.tracered is not None:
        line["breakdown"] = ctx.tracered["breakdown"]
    line["workload"], line["seed"] = cell_name, ctx.seed
    line["window_s"] = ctx.window_s
    line["info"] = {k: v for k, v in ctx.counters.items()
                    if k not in ("attempted", "failed")}
    line["compared"] = {c["name"]: [c["value"], c["limit"]] for c in checks}
    return line
