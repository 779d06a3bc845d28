"""Model step, serving: device time of the window's sound ``prefill`` and
``chunk`` launches over the real prompt tokens they ran (each row's
``tokens``), per 1000 tokens; from the engine's own timeline
(benchmark/harness/launches.py), over the whole window. It counts the
programs that EXECUTED there, where ``prefill_ms_per_ktok`` sets the
trace's prefill time against the prompts admitted in the slice. ``None``
where the program keeps no launch rows, or ran no prompt."""

from benchmark.harness import launches as ln


def read(ctx):
    rows = ln.landed(ctx["res"])
    if not rows:
        return None
    toks = sum(r[ln.ATTRS]["tokens"] for r, sound in rows
               if sound and r[ln.KIND] in ln.PROMPT_KINDS)
    return 1e3 * ln.sound_seconds(rows, ln.PROMPT_KINDS) / (toks / 1000.0) if toks else None
