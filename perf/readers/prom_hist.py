"""The mean or a quantile of a Prometheus histogram's change over the
window. params: ``histogram`` (base name), ``labels`` (exact-match filter),
``stat``: ``"mean"`` (exact: delta sum / delta count) or a quantile in 0..1
(interpolated inside the bucket, so only as fine as the buckets),
``scale``."""


def _match(lab: dict, want: dict) -> bool:
    return all(lab.get(k) == v for k, v in want.items())


def _sum(prom, name, want):
    return sum(v for lab, v in prom.get(name, []) if _match(lab, want))


def read(params: dict, ctx: dict):
    name, want = params["histogram"], params.get("labels", {})
    scale = float(params.get("scale", 1.0))
    a, b = ctx["prom_before"], ctx["prom_after"]
    n = _sum(b, name + "_count", want) - _sum(a, name + "_count", want)
    if n <= 0:
        return None
    if params.get("stat", "mean") == "mean":
        return (_sum(b, name + "_sum", want) - _sum(a, name + "_sum", want)) / n * scale
    q = float(params["stat"])
    edges: dict = {}
    for prom, sign in ((b, 1.0), (a, -1.0)):
        for lab, v in prom.get(name + "_bucket", []):
            if _match(lab, want):
                le = float("inf") if lab["le"] == "+Inf" else float(lab["le"])
                edges[le] = edges.get(le, 0.0) + sign * v
    target, prev_le, prev_c = q * n, 0.0, 0.0
    for le in sorted(edges):
        c = edges[le]
        if c >= target:
            if le == float("inf") or c == prev_c:
                return prev_le * scale
            return (prev_le + (le - prev_le) * (target - prev_c) / (c - prev_c)) * scale
        prev_le, prev_c = le, c
    return None
