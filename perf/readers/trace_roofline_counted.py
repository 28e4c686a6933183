"""A kernel's share of its roofline, in percent, where what a call has to
read depends on values the trace does not carry and the program counts.

``trace_roofline`` (``pattern`` over the instruction's text, ``cost`` module
under ``perf/cost``, the least time of every matching call over its measured
time) with ``counted``: ``{key: {"numerator": counter, "denominator":
counter}}``. Each is the ratio of two counters' changes over the window (a
mean a layer and step where the denominator counts those), and the cost
function finds them under the call's ``counted`` key. The window's mean
stands for the traced interval's calls: fit where the traffic is steady. A
counter the program does not have, or one that did not move, makes the
metric absent, not guessed."""

from perf.readers import prom_delta, trace_roofline


def read(params: dict, ctx: dict):
    t = ctx.get("trace")
    if not t:
        return None
    counted = {key: prom_delta.read(spec, ctx)
               for key, spec in params["counted"].items()}
    if any(v is None for v in counted.values()):
        return None
    calls = [dict(call, counted=counted) for call in t["calls"]]
    return trace_roofline.read(params, dict(ctx, trace=dict(t, calls=calls)))
