"""Small arithmetic kept with the yardstick."""

from __future__ import annotations

import math


def percentile(values, q):
    """Linear-interpolated percentile (q in 0..100) of a non-empty list;
    ``inf`` entries (requests that never answered) sort last."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(v[hi]):
        return v[hi]
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def slices(step_records, t0, width):
    """tokens/s per ``width``-second slice from (t, tokens) records."""
    out = {}
    for t, n in step_records:
        i = int((t - t0) // width)
        out[i] = out.get(i, 0) + n
    return [out.get(i, 0) / width for i in range(max(out) + 1)] if out else []
