"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one ``mv.init``; see ``benchmarks/harness.py`` for the order
of a run. The last line of stdout is the result, one JSON object; every
number compared for ``correct`` is printed beside its limit as the last
lines of stderr and under the result's last key. Exit 0 only where a
result line was printed (``correct`` false is a result). No TPU, fewer
or more chips than the cell is sized for: exit 3, no result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()           # process start, as near as Python gives it

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t0=_T0)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name} = {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
