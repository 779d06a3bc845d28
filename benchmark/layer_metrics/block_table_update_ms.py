"""Cache manager: median over the window's admissions of the time in the
cache manager for one request: the ``cache.*`` spans under its
``engine.admit`` (reserving its pages) plus the ``cache.release_slot`` that
freed the slot it took."""

from benchmark.harness import program_spans as ps


def read(ctx):
    recs = ps.records(ctx["res"])
    if not recs:
        return None
    freed, per_admit = {}, []
    for r in recs:
        for i, sp in enumerate(r.spans):
            if sp[0] == "cache.release_slot":
                freed[sp[4].get("slot")] = ps.seconds(sp)
            elif sp[0] == "engine.admit" and not sp[4].get("requeued"):
                # top-level cache spans only: ensure_blocks lies inside prepare_slot
                own = sum(ps.seconds(r.spans[j]) for j in ps.descendants(r, i)
                          if r.spans[j][0].startswith("cache.")
                          and not r.spans[r.spans[j][3]][0].startswith("cache."))
                per_admit.append(own + freed.pop(sp[4].get("slot"), 0.0))
    return ps.median_ms(per_admit)
