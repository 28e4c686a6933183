"""A kernel's share of its roofline, in percent, from the device trace.

params: ``pattern`` (regular expression over the instruction's text),
``cost`` (module under ``perf/cost``). For every traced call of a matching
op the cost function gives the operations and bytes the algorithm needs
from the call's shapes; the least time the chip could take is the larger of
operations / peak and bytes / bandwidth; the share is the sum of those
least times over the sum of the measured durations. A call whose shapes
the trace does not carry makes the metric absent, not guessed."""

import re

from perf import cost as costs


def read(params: dict, ctx: dict):
    t, peaks = ctx.get("trace"), ctx.get("peaks")
    if not t or not peaks:
        return None
    cost = costs.load(params["cost"], ctx.get("cost_dirs"))
    pat = re.compile(params["pattern"])
    least = measured = 0.0
    for call in t["calls"]:
        if not pat.search(call["text"]):
            continue
        c = cost.cost(call, ctx["cfg"].hf, ctx["cfg"])
        if c is None:
            return None
        least += max(c["flops"] / peaks[c.get("peak", "bf16_flops_per_s")],
                     c["bytes"] / peaks["hbm_bytes_per_s"])
        measured += call["seconds"]
    if measured <= 0:
        return None
    return least / measured * 100.0
