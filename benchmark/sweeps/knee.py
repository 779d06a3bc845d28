"""The knee of an open-loop cell, found ONCE on the chip when the cell is
defined: the highest of a few fixed rates at which the backlog does not grow
over the window and at least 90 % of the requests get a first token within
2 s. The cell then runs at 0.8 of it, written into its traffic file.

    python benchmark/sweeps/knee.py <workload> <out.json> <seconds> <rate> [<rate> ...]

One process: the weights are made once, each rate gets its own engine, server
and window (the ordinary driver), cheapest rate first.
"""

import datetime
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import runner, spec  # noqa: E402


def main():
    wl, out, seconds = sys.argv[1], sys.argv[2], float(sys.argv[3])
    rates = sorted(float(x) for x in sys.argv[4:])
    cell = spec.cell(wl)
    import jax

    runner.place_compile_cache()
    device, _ = runner.device_block(cell["chips"], True)
    base = runner.Run(cell, 7, seconds, False, time.perf_counter())
    runner.build_model(base, 7)
    drv = spec.driver(cell["traffic"]["driver"])
    rows = []
    for rate in rates:
        c = dict(cell, traffic=dict(cell["traffic"], rate_rps=rate))
        r = runner.Run(c, 7, seconds, False, time.perf_counter())
        r.arch, r.hf, r.cfg, r.weights, r.params = (
            base.arch, base.hf, base.cfg, base.weights, base.params)
        res = drv.run(r)
        x = res["extra"]
        grew = x["in_flight_at_close"] > 1.5 * max(x["in_flight_at_open"], 4)
        row = {"rate_rps": rate, "attempted": res["attempted"], "failed": res["failed"],
               "ttft_p95_ms": res["end_to_end"]["ttft_p95_ms"],
               "tpot_p95_ms": res["end_to_end"]["tpot_p95_ms"],
               "sustained": bool(not grew and x["ttft_within_2s_share"] >= 0.9
                                 and res["failed"] == 0), **x}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    table = {"what": "knee sweep: open loop at fixed rates, the ordinary driver",
             "workload": wl, "device": device, "date": datetime.date.today().isoformat(),
             "jax": jax.__version__, "window_s": seconds,
             "rule": "sustained = in-flight at close <= 1.5 x in-flight at open (or 6), "
                     ">= 90 % of first tokens within 2 s, none failed",
             "knee_rps": max(ok) if ok else None,
             "rate_for_the_cell": 0.8 * max(ok) if ok else None, "rows": rows}
    with open(out, "w") as f:
        json.dump(table, f, indent=1)
    print(json.dumps({k: table[k] for k in ("knee_rps", "rate_for_the_cell")}))


if __name__ == "__main__":
    main()
