"""Shared xprof device-time measurement for the perf tools.

A host clock around an asynchronous dispatch measures the enqueue, so
the reported time is hardware ``device_duration_ps`` from a profiler
trace. One process may take as many traces as it likes: repeated
``start_trace``/``stop_trace`` works on the installed stack (jax 0.9.0,
checked on a v5e in PR 21), and the profiler still writes the
``*.trace.json.gz`` this reads. Only the process that holds the chip
can trace it.

Accounting rule (one place, on purpose): sum the ``jit_*`` program
spans. This CHANGED the methodology in round 3 — the tools previously
summed the non-``jit_``/non-``while`` leaf ops, which double-counts
multi-level traces (per-run parent rows + leaves) on big programs and
reads lower than the program span on single-op jits (the r3 regeneration
of TPU_VALIDATE.json re-measured every row under spans). The program
span covers the whole dispatched step on device, for single-op jits and
full train steps alike, and is the number wall-clock comparisons
reproduce.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import tempfile


def trace_device_ms(run_fn, iters: int = 5, program: str = "") -> float:
    """Average device ms per call of ``run_fn`` over ``iters`` traced calls.

    ``program`` narrows the sum to the spans of one jitted program
    (``jit_<program>``), for a ``run_fn`` whose result the closing fetch
    below can only reach through a program of its own (a reshape of a
    table is a copy of the table).

    ``run_fn()`` must dispatch the program under test and return a value
    whose completion the caller's final fetch forces; this helper blocks
    via ``jax.block_until_ready`` + a scalar fetch after the loop.
    Call the function once BEFORE this (compile outside the trace).
    """
    import jax

    trace_dir = tempfile.mkdtemp(prefix="xprof_")
    jax.profiler.start_trace(trace_dir)
    out = None
    for _ in range(iters):
        out = run_fn()
    jax.block_until_ready(out)
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(leaf.reshape(-1)[0])
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                     recursive=True)[0]
    with gzip.open(path) as fh:
        events = json.load(fh)["traceEvents"]
    total = sum(int(e["args"]["device_duration_ps"]) / 1e9 for e in events
                if e.get("ph") == "X"
                and "device_duration_ps" in e.get("args", {})
                and e.get("name", "").startswith("jit_" + program))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return total / iters
