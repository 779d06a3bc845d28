"""The second file that imports the system under test (beside program.py):
it reaches the engine's step records, which the program keeps in a bounded
ring on its metrics registry so that they outlive the engine the driver
frees. A record holds one engine step's spans, rows ``(name, start_ns,
end_ns, parent, attrs)`` on ``time.perf_counter_ns()`` (the clock of the
driver's ``t0``), and the step's work counts. A program without the ring
(the parent of the PR that brought it) gives ``None`` everywhere here, and
each reader then leaves its metric out of the line.
"""

from __future__ import annotations

import statistics


def records(res):
    """The records whose step ended inside the driver's window, oldest
    first; ``None`` where the program keeps none, or where the ring has
    already dropped steps of this window (it no longer reaches back to the
    window's start)."""
    try:
        from shellac_tpu.obs import get_registry
    except ImportError:
        return None
    ring = getattr(get_registry(), "step_records", None)
    if not ring:
        return None
    recs = list(ring)
    t0 = int(res["t0"] * 1e9)
    t1 = t0 + int(res["window_s"] * 1e9)
    if len(recs) == ring.maxlen and recs[0].end_ns > t0:
        return None
    return [r for r in recs if t0 <= r.end_ns <= t1] or None


def total(recs, key):
    return sum(r.counts.get(key, 0) for r in recs)


def share(res, num, den):
    """100 x sum of count ``num`` over sum of count ``den``, over the window."""
    recs = records(res)
    if not recs or not total(recs, den):
        return None
    return 100.0 * total(recs, num) / total(recs, den)


def seconds(sp):
    return (sp[2] - sp[1]) * 1e-9


def median_ms(values):
    return 1e3 * statistics.median(values) if values else None


def descendants(rec, i):
    """Indices of the spans under span ``i`` (parents come before children)."""
    under = {i}
    for j in range(i + 1, len(rec.spans)):
        if rec.spans[j][3] in under:
            under.add(j)
    under.discard(i)
    return sorted(under)
