"""Binding-level data-parallel benchmark driver (VERDICT r1 item 7).

Reproduces the SHAPE of the reference's headline benchmark table
(``binding/python/docs/BENCHMARK.md:33-57``: CIFAR-10 ResNet-32 through the
Python binding, 1-worker baseline / +multiverso overhead / 4-worker
speedup) on this environment:

* rows 1-2 run ResNet-32 (464k params) on the real TPU chip — no-MV
  baseline vs MV with sync every minibatch (binding overhead);
* rows 3-4 run the 4-process data-parallel leg on CPU (the only way to get
  4 real processes here): 1-process baseline vs 4 processes through
  ``jax_ext.MVNetParamManager``, same total work, reporting the speedup.

Writes ``docs/BENCHMARK.md``. Dataset is synthetic CIFAR-shaped (no
egress); accuracies are comparable only within this table.

Usage: python tools/bench_binding.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLE = os.path.join(_REPO, "binding", "python", "examples",
                        "cifar_resnet.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse_result(out: str):
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in output:\n{out[-2000:]}")


def run_single(args, platform: str, timeout=3600):
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=1")
    cmd = [sys.executable, _EXAMPLE] + args
    out = subprocess.run(cmd, env=env, cwd=os.path.dirname(_EXAMPLE),
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"run failed:\n{out.stdout[-2000:]}\n"
                           f"{out.stderr[-2000:]}")
    return _parse_result(out.stdout + out.stderr)


def run_group(args, n: int, timeout=3600):
    port = _free_port()
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "MV_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "MV_NUM_PROCESSES": str(n),
            "MV_PROCESS_ID": str(rank),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        code = ("import sys; sys.path.insert(0, %r); import jax; "
                "jax.config.update('jax_platforms','cpu'); "
                "sys.argv = ['cifar_resnet'] + %r; "
                "import cifar_resnet; sys.exit(cifar_resnet.main())"
                % (os.path.dirname(_EXAMPLE), args))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            cwd=os.path.dirname(_EXAMPLE),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    results = []
    for rank, proc in enumerate(procs):
        out, _ = proc.communicate(timeout=timeout)
        if proc.returncode != 0:
            for p in procs:
                p.kill()
            raise RuntimeError(f"rank {rank} failed:\n{out[-2500:]}")
        results.append(_parse_result(out))
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(_REPO, "docs",
                                                  "BENCHMARK.md"))
    args = ap.parse_args(argv)

    if args.quick:
        tpu_args = ["-epochs", "2", "-train", "2048", "-test", "512"]
        cpu_args = ["-epochs", "2", "-train", "1024", "-test", "256",
                    "-n", "1"]
    else:
        tpu_args = ["-epochs", "3", "-train", "10000", "-test", "2000"]
        cpu_args = ["-epochs", "3", "-train", "2048", "-test", "512",
                    "-n", "1"]

    rows = []
    print("[1/4] TPU 1 proc, no multiverso ...", flush=True)
    rows.append(("1 proc x 1 TPU chip, no multiverso",
                 run_single(tpu_args, "tpu")))
    print("[2/4] TPU 1 proc, multiverso sync=1 ...", flush=True)
    rows.append(("1 proc x 1 TPU chip, multiverso, sync every minibatch",
                 run_single(tpu_args + ["-mv", "1", "-sync_every", "1"],
                            "tpu")))
    print("[3/4] CPU 1 proc, no multiverso ...", flush=True)
    rows.append(("1 proc (CPU), no multiverso", run_single(cpu_args, "cpu")))
    print("[4/4] CPU 4 procs, multiverso sync=1 ...", flush=True)
    group = run_group(cpu_args + ["-mv", "1", "-sync_every", "1"], 4)
    rows.append(("4 procs (CPU), multiverso, sync every minibatch",
                 group[0]))

    cpu_base = rows[2][1]["sec_per_epoch"]
    cpu_dp = rows[3][1]["sec_per_epoch"]
    ncores = os.cpu_count() or 1
    lines = [
        "# Binding benchmark: CIFAR-class ResNet, data-parallel",
        "",
        "Shape-reproduction of the reference's headline table",
        "(`binding/python/docs/BENCHMARK.md:33-57` in the reference:",
        "CIFAR-10 ResNet-32 via the Python binding param manager).",
        "Produced by `tools/bench_binding.py`; model/dataset details in",
        "`binding/python/examples/cifar_resnet.py` (synthetic CIFAR-shaped",
        "data — no egress; accuracies comparable within this table only).",
        "",
        "| configuration | model | params | sec/epoch | test acc |",
        "|---|---|---|---|---|",
    ]
    for name, r in rows:
        lines.append(
            f"| {name} | ResNet-{r['depth']} | {r['params']:,} "
            f"| {r['sec_per_epoch']} | {r['test_acc']:.3f} |")
    lines += [
        "",
        "Environment caveats, so these rows are read correctly:",
        "",
        f"* this box exposes **{ncores} CPU core(s)**, so the 4-process leg",
        "  timeshares one core — the reference's 3.40x/4-GPU speedup is",
        "  physically unreachable here. What the CPU pair DOES measure is",
        "  the binding's data-parallel overhead: 4 processes doing the same",
        "  total work through `MVNetParamManager` (sync every minibatch,",
        f"  aggregation + barrier per step) cost {cpu_dp / cpu_base:.2f}x "
        f"the 1-process wall",
        "  time — i.e. the sync machinery adds "
        f"~{max(cpu_dp / cpu_base - 1, 0) * 100:.0f}% on top of pure",
        "  compute. On independent accelerators (the reference's setup,",
        "  or one process per TPU chip) the same path data-parallelises",
        "  the compute: see `tests/test_multiprocess.py` and",
        "  `docs/DISTRIBUTED.md` for the multi-chip story.",
        "* the +multiverso TPU row pays one host<->device round trip per",
        "  minibatch sync; its size on the current chip is not measured.",
        "",
    ]
    text = "\n".join(lines)
    with open(args.out, "w") as f:
        f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
