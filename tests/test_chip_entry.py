"""Start-up contracts of the chip entry points, checked off the chip.

What the chip needs from start-up can mostly be shown on the CPU: that
importing the package takes no device (a parent that imports it must not
take the chip from its children), that the chip entry points refuse to
run — and print no result — where there is no TPU, that the compile
cache can be placed from outside, and that a process group that was
asked for and did not form is fatal.
"""

from __future__ import annotations

import os
import shutil
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=_REPO, timeout=300, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_overrides)
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_importing_the_package_initialises_no_backend():
    code = (
        "import multiverso_tpu, multiverso_tpu.models, "
        "multiverso_tpu.serving, multiverso_tpu.apps.wordembedding\n"
        "from jax._src import xla_bridge\n"
        "print('BACKENDS_UP', xla_bridge.backends_are_initialized())\n")
    out = _run(["-c", code])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKENDS_UP False" in out.stdout


def test_chip_smoke_refuses_to_run_off_tpu():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert '"ok": false' in last and '"phase": "device"' in last
    assert '"ok": true' not in out.stdout


def test_chip_smoke_alone_prints_no_result(tmp_path):
    """In a directory that holds the script and nothing else of the
    repo there is no system to smoke: non-zero, and no ``"ok": true``."""
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH="")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_bench_prints_no_record_off_tpu():
    out = _run(["bench.py"])
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "pairs_per_sec" not in out.stdout


@pytest.fixture
def restore_cache_dir():
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    import jax

    from multiverso_tpu import runtime

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    placed = runtime._place_compile_cache()
    assert placed == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == placed


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              restore_cache_dir):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and
    the program sets nothing."""
    import jax

    from multiverso_tpu import runtime

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime._place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_a_requested_process_group_that_cannot_form_is_fatal():
    """The backend is already up, so ``jax.distributed.initialize`` must
    refuse; the caller asked for a group (``MV_*`` env), so ``init``
    may not carry on alone as rank 0 of 1."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = (
        "import jax; jax.devices()\n"
        "import multiverso_tpu as mv\n"
        "from multiverso_tpu.log import FatalError\n"
        "try:\n"
        "    mv.init(['t', '-log_level=error'])\n"
        "except FatalError as exc:\n"
        "    print('FATAL', exc)\n"
        "else:\n"
        "    print('CARRIED ON as rank', mv.rank(), 'of', mv.size())\n")
    out = _run(["-c", code], MV_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
               MV_NUM_PROCESSES="2", MV_PROCESS_ID="0")
    assert "CARRIED ON" not in out.stdout, out.stdout
    assert "FATAL" in out.stdout, out.stdout + out.stderr[-2000:]
    assert "jax.distributed.initialize failed" in out.stdout
