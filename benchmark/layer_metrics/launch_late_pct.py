"""Engine: of the launches that landed in the window, the share whose
program had already finished when the host first looked (``late``). The
engine's timeline (benchmark/harness/launches.py) rests on the host waiting
for the device at its pulls: above a few per cent the host is behind the
device and ``window_device_ms_per_tick``, ``prefill_device_ms_per_ktok``,
``prefill_device_pct`` and ``prefill_mfu`` read upper bounds of the times.
``None`` where the program keeps no launch rows."""

from benchmark.harness import launches as ln


def read(ctx):
    rows = ln.landed(ctx["res"])
    if not rows:
        return None
    return 100.0 * sum(bool(r[ln.LATE]) for r, _ in rows) / len(rows)
