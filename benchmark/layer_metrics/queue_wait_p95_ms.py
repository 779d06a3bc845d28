"""Server and scheduler: 95th percentile, over the measured requests, of
the time from when a request was due to when the scheduler put it in a slot
(the generator's lateness plus the server's own queue-wait stamp)."""

from benchmark.harness.stats import percentile


def read(ctx):
    waits = ctx["res"].get("queue_wait_ms")
    return percentile(waits, 95) if waits else None
