"""Test harness: single process, 8 virtual CPU devices.

Mirrors the reference test enabler (SURVEY §4): there, default role=ALL means
one process exercises the full worker->server round-trip with no mpirun; here
one JAX process with ``xla_force_host_platform_device_count=8`` exercises the
full sharded-table path (worker/server mesh axes) with no TPU pod.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import threading

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long benches excluded from the tier-1 run (-m 'not slow')")


@pytest.fixture(autouse=True)
def _lockwatch_guard():
    """Runtime lock-order witness, always on in the suite: every
    framework lock acquisition records into the global order DAG, and a
    test must end with (1) no NEW order violations, (2) the recorded
    graph still acyclic, and (3) every watched lock released — the
    runtime half of the discipline tools/lint.py checks statically.
    A test that deliberately seeds an inversion cleans up with
    ``lockwatch.forget(prefix)`` before returning."""
    from multiverso_tpu.analysis import lockwatch

    lockwatch.enable()
    before = lockwatch.violation_count()
    yield
    after = lockwatch.violations()
    new = after[before:] if len(after) > before else []
    assert not new, (
        "test introduced lock-order violation(s): "
        + "; ".join(v.describe() for v in new))
    cycles = lockwatch.check_acyclic()
    assert not cycles, f"lock order graph has cycle(s): {cycles}"
    # daemon threads may hold a watched lock transiently mid-poll; only
    # a hold persisting across the grace window is a leak/wedge
    lockwatch.assert_released(timeout_s=5.0)


@pytest.fixture(autouse=True)
def _no_stray_nondaemon_threads():
    """Test-isolation guard: a test must not leave NEW non-daemon
    threads running — a leaked reporter/exporter thread would block
    interpreter exit and bleed state into every later test. (The
    framework's own worker threads are all daemons; Dashboard.reset()
    additionally detaches any attached MetricsExporter/watchdog.)"""
    before = set(threading.enumerate())
    yield
    strays = [t for t in threading.enumerate()
              if t not in before and not t.daemon and t.is_alive()]
    for t in strays:                 # grace: let clean shutdowns finish
        t.join(timeout=5)
    strays = [t for t in strays if t.is_alive()]
    assert not strays, (
        f"test leaked non-daemon thread(s): {[t.name for t in strays]}")


@pytest.fixture()
def mv_session():
    """Fresh framework session per test (init -> yield -> shutdown)."""
    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.runtime import Session

    # Reset leftover state from a prior test's session.
    Session._instance = None
    Dashboard.reset()
    mv.set_flag("sync", False)
    mv.set_flag("ma", False)
    mv.set_flag("updater_type", "default")
    mv.set_flag("mesh_shape", "")
    mv.init()
    yield mv
    mv.shutdown()
    Session._instance = None
