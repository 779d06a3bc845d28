"""Engine: decode rows that produced a token over decode rows computed (the
program's counts ``decode_valid_ticks`` over ``decode_slot_ticks``: ticks x
slots of every window synced in the run's window). What is missing from 100
is slots that were empty or waiting for a prefill when the window was
dispatched, and rows frozen after their request ended."""

from benchmark.harness import program_spans


def read(ctx):
    return program_spans.share(ctx["res"], "decode_valid_ticks", "decode_slot_ticks")
