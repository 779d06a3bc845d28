"""The shrink of ``evabyte`` and ``batch-bytes`` at which the cell
``evabyte-batch-bytes`` runs on the CPU in seconds (the sibling of tiny.py,
which a PR that adds a cell may not edit). Every prompt is still longer than
a window, so every request prefills pooled rows and every tick attends both
kinds of state. Rehearse with

    JAX_PLATFORMS=cpu python benchmark/tests/tiny_evabyte.py [seed] [seconds] [trace]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "evabyte-batch-bytes"
TRAFFIC = {
    "prompt_tokens": {"min": 40, "max": 150, "n": 16},
    "output_tokens": {"min": 8, "max": 40, "n": 16},
    "check_sample": 3,
}
CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "num_pred_heads": 3,
    "window_size": 32, "chunk_size": 4, "torch_dtype": "float32",
    "serving": {"n_slots": 4, "block_size": 32, "decode_ticks": 2},
}


def rehearse(seed=1, seconds=3.0, trace=False, **kw):
    from benchmark.harness import runner

    return runner.run_cell(WORKLOAD, seed, seconds, trace, require_chip=False,
                           config_override=CONFIG, traffic_override=TRAFFIC, **kw)


if __name__ == "__main__":
    a = sys.argv[1:]
    sys.exit(rehearse(int(a[0]) if a else 1, float(a[1]) if len(a) > 1 else 3.0,
                      bool(int(a[2])) if len(a) > 2 else False))
