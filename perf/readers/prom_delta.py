"""A counter's change over the window, or the ratio of two counters'
changes. params: ``counter`` | (``numerator``, ``denominator``); optional
``labels`` (exact-match filter) and ``scale``."""


def _total(prom: dict, name: str, labels: dict) -> float:
    return sum(v for lab, v in prom.get(name, [])
               if all(lab.get(k) == want for k, want in labels.items()))


def read(params: dict, ctx: dict):
    labels = params.get("labels", {})

    def delta(name):
        if name not in ctx["prom_after"]:
            return None
        return (_total(ctx["prom_after"], name, labels)
                - _total(ctx["prom_before"], name, labels))

    scale = float(params.get("scale", 1.0))
    if "counter" in params:
        d = delta(params["counter"])
        return None if d is None else d * scale
    num, den = delta(params["numerator"]), delta(params["denominator"])
    if num is None or not den:
        return None
    return num / den * scale
