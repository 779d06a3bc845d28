"""The shrink of ``ouro-2.6b`` and ``batch-reason`` at which the cell
``ouro-2.6b-batch-reason`` runs on the CPU in seconds (a sibling of tiny.py,
which a PR that adds a cell may not edit). Three layers run three times, so
every tick still walks the stack more than once over nine cached layers, and
outputs are still longer than prompts. Rehearse with

    JAX_PLATFORMS=cpu python benchmark/tests/tiny_ouro.py [seed] [seconds] [trace]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "ouro-2.6b-batch-reason"
TRAFFIC = {
    "prompt_tokens": {"min": 8, "max": 31, "n": 16},
    "output_tokens": {"min": 16, "max": 48, "n": 16},
    "check_sample": 3,
}
CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
    "layer_types": ["full_attention"] * 3, "max_window_layers": 3,
    "total_ut_steps": 3, "vocab_size": 256, "max_position_embeddings": 512,
    "torch_dtype": "float32",
    "serving": {"n_slots": 4, "block_size": 16, "decode_ticks": 2},
}


def rehearse(seed=1, seconds=3.0, trace=False, **kw):
    from benchmark.harness import runner

    return runner.run_cell(WORKLOAD, seed, seconds, trace, require_chip=False,
                           config_override=CONFIG, traffic_override=TRAFFIC, **kw)


if __name__ == "__main__":
    a = sys.argv[1:]
    sys.exit(rehearse(int(a[0]) if a else 1, float(a[1]) if len(a) > 1 else 3.0,
                      bool(int(a[2])) if len(a) > 2 else False))
