"""From the profiler's trace to numbers. Kept with the benchmark so that
every PR computes the same number the same way.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a plain
structure (planes -> lines -> events with start and duration in ns);
``reduce_planes`` turns that into busy time (the union of the intervals in
which an operation ran on a device, averaged over the devices), device time
by program and by operation (self time: an operation that contains others,
such as a ``while``, is charged only what its children do not cover), and
the longest idle gaps named by the programs on either side and by what the
host thread was in. benchmark/tests checks it on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

from benchmark.harness.stats import union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise SystemExit(f"the profiler wrote no trace under {trace_dir}")
    return files[-1]


def load(path):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        is_dev = bool(DEVICE_PLANE.match(p.name))
        if not is_dev and p.name != "/host:CPU":
            continue
        lines = []
        for ln in p.lines:
            if is_dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            ev = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events]
            if not is_dev:
                ev = [e for e in ev if e[2] > 0]
            lines.append({"name": ln.name, "events": ev})
        planes.append({"name": p.name, "lines": lines})
    return planes


def _clean(name, n=64):
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[^A-Za-z0-9_.:()|-]", "_", name)[:n]


def self_times(events):
    """(name, self ns) for nested events on one line."""
    out, stack = [], []          # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            out.append((stack[-1][0], stack[-1][2]))
            stack.pop()
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend((s[0], s[2]) for s in stack)
    return out


def program_class(module_name):
    n = module_name.lower()
    if "decode" in n:
        return "decode"
    if "prefill" in n:
        return "prefill"
    if "train_step" in n:
        return "train_step"
    return "other"


def reduce_planes(planes, window_s, n_devices=1):
    devs = sorted((p for p in planes if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))[:n_devices]
    if not devs:
        raise SystemExit("the trace holds no device plane: nothing ran on the chip")
    line = lambda p, n: next((l["events"] for l in p["lines"] if l["name"] == n), [])
    busy = [union_seconds([(s, s + d) for _, s, d in line(p, OPS_LINE)]) / 1e9 for p in devs]
    d0 = devs[0]
    ops, mods = line(d0, OPS_LINE), sorted(line(d0, MODULES_LINE), key=lambda e: e[1])

    by_op = {}
    for name, ns in self_times(ops):
        by_op[_clean(name)] = by_op.get(_clean(name), 0) + ns
    top_ops = sorted(([k, v / 1e9] for k, v in by_op.items()), key=lambda kv: -kv[1])

    by_class = {}
    for name, _, d in mods:
        c = program_class(name)
        ent = by_class.setdefault(c, {"seconds": 0.0, "runs": 0, "durations": []})
        ent["seconds"] += d / 1e9
        ent["runs"] += 1
        ent["durations"].append(d / 1e9)
    for ent in by_class.values():
        # a program in flight when the trace starts or stops shows as a short
        # event: the median run is a whole one, the mean is not
        ent["median_s"] = statistics.median(ent.pop("durations"))

    # idle gaps on device 0, named by the programs either side and the host
    iv = sorted((s, s + d) for _, s, d in ops)
    merged = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    host = [e for p in planes if p["name"] == "/host:CPU" for l in p["lines"]
            for e in l["events"]]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])),
                  reverse=True)[:10]
    idle_gaps = []
    for length, gs, ge in gaps:
        before = next((m[0] for m in reversed(mods) if m[1] + m[2] <= gs + 1000), "start")
        after = next((m[0] for m in mods if m[1] >= ge - 1000), "end")
        mid = (gs + ge) // 2
        inside = [h for h in host if h[1] <= mid < h[1] + h[2]]
        what = min(inside, key=lambda h: h[2])[0] if inside else "host:unattributed"
        short = lambda m: _clean(re.sub(r"^jit_+", "", m), 18)
        idle_gaps.append([f"{_clean(what, 24)}|{short(before)}>{short(after)}", length / 1e9])
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "busy_by_device": busy, "top_ops": top_ops, "programs": by_class,
            "idle_gaps": idle_gaps, "n_op_events": len(ops)}


def reduce(trace_dir, window_s, n_devices=1):
    return reduce_planes(load(find_xplane(trace_dir)), window_s, n_devices)
