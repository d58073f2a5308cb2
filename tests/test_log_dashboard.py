"""Logger + dashboard tests (reference: util/log.h, dashboard.h)."""

import os
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from multiverso_tpu.dashboard import Dashboard, Monitor, Timer, monitor
from multiverso_tpu.log import FatalError, Log, LogLevel, check, check_notnull


def test_fatal_raises():
    Log.reset_kill_fatal(False)
    with pytest.raises(FatalError):
        Log.fatal("boom %d", 42)


def test_check_macros():
    check(True)
    with pytest.raises(FatalError):
        check(False, "invariant broken")
    assert check_notnull(5) == 5
    with pytest.raises(FatalError):
        check_notnull(None, "ptr")


def test_log_file_sink(tmp_path):
    path = str(tmp_path / "mv.log")
    # the level is process-global: an earlier test of this xdist worker
    # that ran mv.init([..., "-log_level=error"]) would silence info()
    level = Log.logger().level
    Log.reset_log_level(LogLevel.INFO)
    Log.reset_log_file(path)
    try:
        Log.info("hello file sink")
    finally:
        Log.reset_log_file("")  # detach
        Log.reset_log_level(level)
    with open(path) as f:
        content = f.read()
    assert "hello file sink" in content
    assert "[INFO]" in content


def test_timer_measures():
    t = Timer()
    time.sleep(0.01)
    assert t.elapse_ms() >= 5


def test_monitor_accumulates():
    Dashboard.reset()
    mon = Monitor("unit_test_mon")
    for _ in range(3):
        mon.begin()
        time.sleep(0.002)
        mon.end()
    assert mon.count == 3
    assert mon.total_ms > 0
    assert abs(mon.average_ms() - mon.total_ms / 3) < 1e-9
    assert "unit_test_mon" in Dashboard.watch("unit_test_mon")
    stats = Dashboard.stats("unit_test_mon")
    assert stats["count"] == 3


def test_monitor_context_manager_and_display():
    Dashboard.reset()
    with monitor("span_test"):
        time.sleep(0.002)
    with monitor("span_test"):
        pass
    assert Dashboard.stats("span_test")["count"] == 2
    text = Dashboard.display(emit=lambda *a: None)
    assert "span_test" in text
    assert Dashboard.watch("missing") == "[missing] not monitored"


def test_profile_trace_writes_xplane(tmp_path):
    import os

    import jax.numpy as jnp

    from multiverso_tpu.dashboard import Dashboard, profile_trace

    logdir = str(tmp_path / "trace")
    with profile_trace(logdir, name="PROF_SPAN"):
        jnp.ones((64, 64)).sum().block_until_ready()
    found = []
    for root, _, files in os.walk(logdir):
        found.extend(files)
    assert found, "profiler trace produced no files"
    assert "PROF_SPAN" in Dashboard.display()


def test_trace_summary_tool(tmp_path):
    """tools/trace_summary.py parses a profile_trace capture and reports
    hardware-measured device durations by source/op."""
    import contextlib
    import io as _io

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.dashboard import profile_trace

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((128, 128))
    float(f(x))   # compile outside the trace
    with profile_trace(str(tmp_path)):
        float(f(x))

    import runpy
    import sys as _sys

    out = _io.StringIO()
    argv = _sys.argv
    _sys.argv = ["trace_summary", str(tmp_path), "--by", "op"]
    try:
        with contextlib.redirect_stdout(out):
            with pytest.raises(SystemExit) as exc:
                runpy.run_path(
                    os.path.join(_REPO, "tools", "trace_summary.py"),
                    run_name="__main__")
            assert exc.value.code in (0, None)
    finally:
        _sys.argv = argv
    assert "device time total" in out.getvalue()
