"""Engine: median over the window's steps of the ``engine.step`` span less
its two pulls (the ``engine.wait_window`` and ``engine.wait_prefill``
descendants): the part of a step the host spends outside them. That is the
host work a decode window has to hide PLUS whatever time the step's eager
device calls (the block-table scatters under ``cache.*``, the per-slot
sampling-state writes under ``engine.admit``) spend blocked behind the
window queued ahead of them, which no host clock can tell from work. While
those writes block, this reads about a whole step (PERF.md, section 5)."""

from benchmark.harness import program_spans as ps

WAITS = ("engine.wait_window", "engine.wait_prefill")


def read(ctx):
    recs = ps.records(ctx["res"])
    if not recs:
        return None
    host = []
    for r in recs:
        waited = sum(ps.seconds(r.spans[j]) for j in ps.descendants(r, r.root)
                     if r.spans[j][0] in WAITS)
        host.append((r.end_ns - r.start_ns) * 1e-9 - waited)
    return ps.median_ms(host)
