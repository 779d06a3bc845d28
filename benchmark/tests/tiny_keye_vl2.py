"""The shrink of ``keye-vl-2.0-30b-a3b`` and ``batch-long`` at which the cell
``keye-vl-2.0-30b-a3b-batch-long`` runs on the CPU in seconds (a sibling of
tiny.py, which a PR that adds a cell may not edit). Every prompt is still at
least twice the rows kept and longer than a prefill chunk, so every request
prefills in cached chunks whose queries choose, and every decode tick scores,
chooses and attends its chosen rows. Rehearse with

    JAX_PLATFORMS=cpu python benchmark/tests/tiny_keye_vl2.py [seed] [seconds] [trace]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORKLOAD = "keye-vl-2.0-30b-a3b-batch-long"
TRAFFIC = {
    "prompt_tokens": {"min": 32, "max": 150, "n": 16},
    "output_tokens": {"min": 8, "max": 40, "n": 16},
    "check_sample": 3,
}
CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "vocab_size": 256, "max_window_layers": 2,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2, "topk": 16},
    "torch_dtype": "float32",
    "serving": {"n_slots": 4, "block_size": 8, "decode_ticks": 2, "prefill_chunk": 32},
}


def rehearse(seed=1, seconds=3.0, trace=False, **kw):
    from benchmark.harness import runner

    return runner.run_cell(WORKLOAD, seed, seconds, trace, require_chip=False,
                           config_override=CONFIG, traffic_override=TRAFFIC, **kw)


if __name__ == "__main__":
    a = sys.argv[1:]
    sys.exit(rehearse(int(a[0]) if a else 1, float(a[1]) if len(a) > 1 else 3.0,
                      bool(int(a[2])) if len(a) > 2 else False))
