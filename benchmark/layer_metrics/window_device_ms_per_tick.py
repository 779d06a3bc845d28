"""Model step, serving: median over the window's sound ``window`` launches
of a launch's device time over the ticks it ran: ``decode_step_ms`` taken
from the engine's own timeline (benchmark/harness/launches.py), so without
the profiler and over the WHOLE window, where the device trace holds the
first seconds of it. ``None`` where the program keeps no launch rows."""

from benchmark.harness import launches as ln
from benchmark.harness import program_spans as ps


def read(ctx):
    rows = ln.landed(ctx["res"])
    if not rows:
        return None
    return ps.median_ms([ln.device_s(r) / r[ln.ATTRS]["ticks"] for r, sound in rows
                         if sound and r[ln.KIND] == "window"])
