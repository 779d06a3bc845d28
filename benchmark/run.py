"""The benchmark's one command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output (see
benchmark/README.md). Exits non-zero, printing no result, where JAX finds no
accelerator, fewer chips than the cell asks for, or no program to measure.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    from benchmark.harness import runner

    return runner.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
