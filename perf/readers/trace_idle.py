"""The device's idle share of the traced interval, in percent: 1 - (union
of the intervals in which an operation ran) / interval."""


def read(params: dict, ctx: dict):
    t = ctx.get("trace")
    if not t or not t["window_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
