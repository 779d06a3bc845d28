"""Engine: real prompt tokens over bucketed (padded) prompt tokens of the
prefill programs dispatched in the run's window (the program's counts
``prefill_tokens`` over ``prefill_padded_tokens``)."""

from benchmark.harness import program_spans


def read(ctx):
    return program_spans.share(ctx["res"], "prefill_tokens", "prefill_padded_tokens")
