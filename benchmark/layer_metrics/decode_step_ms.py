"""Model step, serving: device time of one decode-window program in the
traced window (the median run: a window cut by the trace's edge is short)
over the decode ticks it runs."""


def read(ctx):
    p = ctx["trace"]["programs"].get("decode")
    if not p or not p["runs"]:
        return None
    return 1e3 * p["median_s"] / ctx["res"]["decode_ticks"]
