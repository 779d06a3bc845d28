"""The control of a serving cell, on the chip at the cell's own size:

    python benchmark/tests/control_on_chip.py <workload> <seed> [seconds]

One ordinary run of the cell with a short window; after it, the same sample
of served requests goes through the reference twice: in float32 (the
program's reading, ``logit_gap_max``) and with every matmul operand rounded
to int8 with one scale per row (the control's reading,
``control_logit_gap_max``: at each position, the gap of the token that the
lower precision puts first). The benchmark's own runs never run this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.run_cell(sys.argv[1], int(sys.argv[2]),
                             float(sys.argv[3]) if len(sys.argv) > 3 else 10.0,
                             False, control=True))
