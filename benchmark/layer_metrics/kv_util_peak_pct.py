"""Cache manager: peak of cache_backend.utilization() over the window."""


def read(ctx):
    rows = ctx["res"]["steps"]
    return 100.0 * max(r["kv_util"] for r in rows) if rows else None
