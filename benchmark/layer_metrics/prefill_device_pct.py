"""Model step, serving: of the device time of the window's sound launches,
the share that ``prefill`` and ``chunk`` launches took, all decoding slots
waiting: what is missing from 100 is the decode programs' share of busy
time, the term of PERF.md's identity that no other metric reads. From the
engine's own timeline (benchmark/harness/launches.py), the whole window.
``None`` where the program keeps no launch rows."""

from benchmark.harness import launches as ln


def read(ctx):
    rows = ln.landed(ctx["res"])
    if not rows:
        return None
    every = ln.sound_seconds(rows, ln.PROMPT_KINDS + ("window",))
    return 100.0 * ln.sound_seconds(rows, ln.PROMPT_KINDS) / every if every else None
