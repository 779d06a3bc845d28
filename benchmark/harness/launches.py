"""The engine's own device timeline, read through ``program_spans.records``:
every step record of a program that keeps one lists the programs the step
dispatched as rows ``[seq, kind, program, dispatched_ns, busy_from_ns,
done_ns, late, attrs]`` on the clock of the driver's ``t0``. ``kind`` is
``prefill`` (a whole prompt), ``chunk`` (a continuation at an ``offset``) or
``window`` (a decode window of ``ticks`` ticks); ``done_ns`` is when the host,
waiting at a pull it makes anyway, found the program finished (0: never
looked), ``busy_from_ns`` the later of its predecessor's ``done_ns`` and its
own dispatch, ``late`` that it had already finished when the host first
looked, so that its ``done_ns`` is only an upper bound. A launch is *sound*
when it and its predecessor (the row numbered one less) both landed and
neither was late: ``done_ns - busy_from_ns`` is then its device time. A
program without such rows (the parent of the PR that brought them) gives
``None`` here, and each reader then leaves its metric out of the line.
"""

from __future__ import annotations

from benchmark.harness import program_spans as ps

SEQ, KIND, PROGRAM, DISPATCHED, BUSY_FROM, DONE, LATE, ATTRS = range(8)
PROMPT_KINDS = ("prefill", "chunk")


def landed(res, t0=None, t1=None):
    """``(row, sound)`` of every launch that landed inside the driver's window
    (or inside ``t0..t1``, seconds on its clock), in dispatch order; ``None``
    where the program keeps no rows or none landed there."""
    recs = ps.records(res)
    if not recs or not hasattr(recs[0], "launches"):
        return None
    lo = int((res["t0"] if t0 is None else t0) * 1e9)
    hi = int((res["t0"] + res["window_s"] if t1 is None else t1) * 1e9)
    out, prev = [], None
    for rec in recs:
        for row in rec.launches:
            ok = bool(row[DONE]) and not row[LATE]
            sound = (ok and prev is not None and prev[SEQ] + 1 == row[SEQ]
                     and bool(prev[DONE]) and not prev[LATE])
            if row[DONE] and lo <= row[DONE] <= hi:
                out.append((row, sound))
            prev = row
    return out or None


def device_s(row):
    return (row[DONE] - row[BUSY_FROM]) * 1e-9


def sound_seconds(rows, kinds):
    """Summed device seconds of the sound launches of ``kinds``."""
    return sum(device_s(r) for r, sound in rows if sound and r[KIND] in kinds)


def timeline(rows):
    """The rows' time as intervals ``(start_ns, end_ns, drained)`` in order:
    before each launch the stretch the device had nothing queued (its
    predecessor finished, it was not yet dispatched), then the launch from
    ``busy_from_ns`` to ``done_ns``. A stretch before a launch whose
    predecessor is not among the rows is not known and is left out."""
    out, prev = [], None
    for row, _ in rows:
        if prev is not None and prev[SEQ] + 1 == row[SEQ] \
                and row[DISPATCHED] > prev[DONE]:
            out.append((prev[DONE], row[DISPATCHED], True))
        out.append((row[BUSY_FROM], row[DONE], False))
        prev = row
    return out
