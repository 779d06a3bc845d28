"""Cache manager: of the rows that the window's decode queries attended,
the share that were pooled rows of earlier windows (the program's counts
``eva_summary_rows`` over ``eva_window_rows`` + ``eva_summary_rows``, which
the 'eva' cache backend takes from the lengths of every slot-tick that
produced a token). Shaped by the traffic; it proves on every later PR that
the cell still runs the mechanism. ``None`` where the program keeps no such
counts."""

from benchmark.harness import program_spans as ps


def read(ctx):
    recs = ps.records(ctx["res"])
    if not recs:
        return None
    pooled = ps.total(recs, "eva_summary_rows")
    rows = pooled + ps.total(recs, "eva_window_rows")
    return 100.0 * pooled / rows if rows else None
