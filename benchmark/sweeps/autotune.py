"""The program's own decode_ticks sweep, run ONCE on the chip when a cell is
defined; its winner is pinned in the configuration file and no run sweeps.

    python benchmark/sweeps/autotune.py <workload> [out.json]
"""

import datetime
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import program, runner, spec  # noqa: E402


def main():
    wl = sys.argv[1]
    out = sys.argv[2] if len(sys.argv) > 2 else None
    cell = spec.cell(wl)
    import jax

    runner.place_compile_cache()
    device, _ = runner.device_block(cell["chips"], True)
    r = runner.Run(cell, 0, 0.0, False, 0.0)
    runner.build_model(r, 0)
    eng = program.build_engine(cell, r.cfg, r.params, 0, for_server=False)
    from shellac_tpu.inference.autotune import autotune_decode_ticks

    res = autotune_decode_ticks(eng, candidates=(1, 2, 4, 8, 16, 32))
    table = {
        "what": "shellac_tpu.inference.autotune.autotune_decode_ticks on the cell's engine",
        "workload": wl, "config": cell["config_name"], "device": device,
        "date": datetime.date.today().isoformat(), "jax": jax.__version__,
        "n_slots": eng.n_slots, "max_len": eng.max_len,
        "probe": "32-token prompts, 3 windows past a warm-up window, every slot busy",
        "best": res.best,
        "tokens_per_s": {str(k): v for k, v in res.measurements.items()},
        "seconds": {str(k): v for k, v in res.elapsed.items()},
    }
    text = json.dumps(table, indent=1)
    print(text)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
