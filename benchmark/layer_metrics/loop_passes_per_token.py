"""Model step, serving: passes over the layer stack per token delivered. The
program's count ``loop_passes`` (the cache backend's, for a looped model:
``steps`` for every decode slot-tick that produced a token, from lengths the
host has) over the tokens the driver saw delivered, the whole window. It reads
the model's ``total_ut_steps`` when every slot-tick that ran delivered, a
little under it because a request's first token comes from its prefill (whose
passes are not decode ticks), and more where ticks produce tokens that are
never handed out. It proves on every later PR that the cell still walks the
stack that often. ``None`` where the program keeps no such count, or counted
no pass (a model without a loop)."""

from benchmark.harness import program_spans as ps


def read(ctx):
    recs = ps.records(ctx["res"])
    delivered = ctx["res"].get("delivered")
    if not recs or not delivered:
        return None
    passes = sum(r.counts.get("loop_passes", 0) for r in recs)
    return passes / delivered if passes else None
