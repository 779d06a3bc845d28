"""Quartile spreads of a cell's runs, as the bound rule reads them.

    python benchmark/sweeps/spread.py <runs.jsonl>

Each line of the file is a run's result line with ``set`` and ``seed`` added.
For every metric and each set: the median and the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; the bound is about five times the widest.
"""

import json
import statistics
import sys


def main():
    rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip().startswith("{")]
    broken = [r for r in rows if "metrics" not in r]
    rows = [r for r in rows if "metrics" in r]
    if broken:
        print("runs without a result line:", [(r.get("set"), r.get("seed")) for r in broken])
    sets = sorted({r["set"] for r in rows})
    names = sorted({k for r in rows for k in r["metrics"]})
    for name in names:
        widest = 0.0
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in rows
                    if r["set"] == s and name in r["metrics"]]
            if len(vals) < 2:
                continue
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            spread = (q[2] - q[0]) / med
            widest = max(widest, spread)
            print(f"{name} set {s}: n={len(vals)} median={med:.4f} spread={100 * spread:.2f}% "
                  f"min={min(vals):.4f} max={max(vals):.4f}")
        print(f"{name}: widest spread {100 * widest:.2f}% -> bound about {100 * 5 * widest:.1f}%")
    bad = [(r["set"], r["seed"]) for r in rows if not r["correct"]]
    gaps = [r["check"]["logit_gap_max"]["value"] for r in rows if "check" in r]
    print("not correct:", bad, "| logit_gap_max over runs: max", max(gaps) if gaps else None)


if __name__ == "__main__":
    main()
