"""Cache manager: of the rows that the window's indexers scored (every row of
every decode query's context), the share its queries then attended (the
program's counts ``dsa_selected_rows`` over ``dsa_index_rows``, which the
'paged' cache backend takes, for a model with an indexer, from the lengths of
every slot-tick that produced a token). Shaped by the traffic; it proves on
every later PR that the cell still runs the mechanism (100 would mean no
choice was made). ``None`` where the program keeps no such counts."""

from benchmark.harness import program_spans as ps


def read(ctx):
    recs = ps.records(ctx["res"])
    if not recs:
        return None
    scored = ps.total(recs, "dsa_index_rows")
    return 100.0 * ps.total(recs, "dsa_selected_rows") / scored if scored else None
