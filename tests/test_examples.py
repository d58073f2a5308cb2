"""Binding examples smoke tests (reference ``binding/python/examples``).

Runs each example as a real subprocess the way a user would, on the CPU
backend. The examples assert their own convergence (test accuracy), so a
zero exit code means the end-to-end data-parallel loop worked.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = os.path.join(_REPO, "binding", "python", "examples")


def _run_example(name: str, timeout: float = 420.0):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "binding", "python"), _REPO,
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, name], cwd=_EXAMPLES, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_logistic_regression_example():
    result = _run_example("logistic_regression.py")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "test accuracy" in result.stdout


def test_jax_data_parallel_example():
    result = _run_example("jax_data_parallel.py")
    assert result.returncode == 0, result.stderr[-2000:]


def test_cnn_example():
    pytest.importorskip("torch")
    result = _run_example("cnn.py")
    assert result.returncode == 0, result.stderr[-2000:]
