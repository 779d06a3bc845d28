"""Engine: occupied slots over n_slots, averaged over the window's steps
(closed loop) or over 50-ms samples of the slot table (open loop)."""


def read(ctx):
    rows = ctx["res"]["steps"]
    if not rows:
        return None
    return 100.0 * sum(r["occupied"] for r in rows) / (len(rows) * ctx["res"]["n_slots"])
