"""Device: share of the window in which the device had nothing of the
engine's queued: between one program found finished and the dispatch of
the next, where the dispatch came later (benchmark/harness/launches.py's
``timeline``), over the time the window's landed launches span:
``device_idle_pct`` from the engine's own timeline, over the whole window.
The one interval that holds the harness's ``trace_t1`` is left out of both
sums: there ``stop_trace`` stalls the host for seconds, which is the
profiler's cost, not the program's. ``None`` where the program keeps no
launch rows."""

from benchmark.harness import launches as ln


def read(ctx):
    rows = ln.landed(ctx["res"])
    if not rows:
        return None
    t1 = ctx["run"].trace_t1
    stall = None if t1 is None else int(t1 * 1e9)
    drained = whole = 0
    for start, end, idle in ln.timeline(rows):
        if stall is not None and start <= stall <= end:
            continue
        whole += end - start
        drained += (end - start) if idle else 0
    return 100.0 * drained / whole if whole else None
