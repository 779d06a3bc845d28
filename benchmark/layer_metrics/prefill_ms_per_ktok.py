"""Model step, serving: device time of the prefill programs in the traced
window over the prompt tokens admitted in it, per 1000 tokens."""


def read(ctx):
    p = ctx["trace"]["programs"].get("prefill")
    r = ctx["run"]
    if not p or r.trace_t0 is None:
        return None
    toks = sum(n for t, n in ctx["res"]["admits"] if r.trace_t0 <= t <= r.trace_t1)
    return 1e3 * p["seconds"] / (toks / 1000.0) if toks else None
