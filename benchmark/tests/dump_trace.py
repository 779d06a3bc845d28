"""Print the shape of the newest profiler trace (planes, lines, a few events)
and write a small cut of it as JSON: the fixture the trace test reads.

    python benchmark/tests/dump_trace.py <out.json> [trace_dir]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import runner, trace  # noqa: E402


def main():
    out = sys.argv[1]
    d = sys.argv[2] if len(sys.argv) > 2 else os.path.join(runner.scratch_dir(), "trace")
    import jax

    path = trace.find_xplane(d)
    data = jax.profiler.ProfileData.from_file(path)
    for p in data.planes:
        print("PLANE", p.name)
        for ln in p.lines:
            ev = list(ln.events)
            print("  LINE", repr(ln.name), len(ev),
                  [(e.name[:60], int(e.start_ns), int(e.duration_ns)) for e in ev[:3]])
    planes = trace.load(path)
    t0 = min(e[1] for p in planes for l in p["lines"] for e in l["events"])
    cut = [{"name": p["name"], "lines": [
        {"name": l["name"], "events": [[e[0][:80], e[1] - t0, e[2]] for e in l["events"]
                                       if e[1] - t0 < 400_000_000][:4000]}
        for l in p["lines"]]} for p in planes]
    with open(out, "w") as f:
        json.dump(cut, f)
    print("wrote", out, os.path.getsize(out))


if __name__ == "__main__":
    main()
