"""Rehearse a cell without a chip, at tiny size:

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py <workload> [seed] [seconds] [trace]

It skips the look for a chip and shrinks the configuration and the mix
(benchmark/tests/tiny.py); everything else is the run the chip gets. Its
numbers are rehearsal output, never device metrics.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import runner, spec  # noqa: E402
from benchmark.tests import tiny  # noqa: E402


def main():
    wl = sys.argv[1]
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 3.0
    trace = bool(int(sys.argv[4])) if len(sys.argv) > 4 else False
    cfg = spec.cell(wl)["config_name"]
    return runner.run_cell(wl, seed, seconds, trace, require_chip=False,
                           config_override=tiny.CONFIG[cfg],
                           traffic_override=tiny.TRAFFIC)


if __name__ == "__main__":
    sys.exit(main())
