"""A quantile of a named span's duration over the requests that started in
the window, from the engine's ``GET /debug/requests``. params: ``span``,
``q`` (0..100)."""

from perf.client import percentile


def read(params: dict, ctx: dict):
    lo, hi = ctx["window_wall"]
    values = [
        s["duration_ms"]
        for r in (ctx["spans"].get("requests") or [])
        if lo <= r.get("start_time", 0) <= hi
        for s in r.get("spans", [])
        if s.get("name") == params["span"]
    ]
    if not values:
        return None
    return percentile(values, float(params["q"]))
