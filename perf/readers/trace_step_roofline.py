"""A kernel's share of its roofline, in percent, where the kernel's bytes
depend on what the step held and not on its shapes: per decode step that
lies wholly inside the traced interval, the cost function gives the
operations and bytes the algorithm needs from the step's own metadata
(``pst.step_info``: rows, context lengths), the least time is the larger of
operations / peak and bytes / bandwidth, and the measured time is the
kernel's time inside the program that step launched
(``perf/host_trace.py``). The share is the sum of the least times over the
sum of the measured. params: ``ops`` (regular expression over a device
operation's text: the operations whose time inside each step is the
kernel's, such as ``^%paged_attn_decode``; ``host_trace.reduce`` times
them for every metric of the cell that names some), ``cost`` (module under
``perf/cost``; it is handed the step's ``pst.step_info`` fields whole, with
``module_s`` and ``ops_s``). A step the cost function cannot cost makes the
metric absent, not guessed, and so does a reduction that did not time
``ops``."""

from perf import cost as costs
from perf import host_trace


def read(params: dict, ctx: dict):
    t, peaks = host_trace.of_run(ctx), ctx.get("peaks")
    if not t or not peaks:
        return None
    cost = costs.load(params["cost"], ctx.get("cost_dirs"))
    least = measured = 0.0
    for step in t["decode_steps"]:
        seconds = step.get("ops_s", {}).get(params["ops"])
        if seconds is None:
            return None
        if seconds <= 0:
            continue
        c = cost.cost(step, ctx["cfg"].hf, ctx["cfg"])
        if c is None:
            return None
        least += max(c["flops"] / peaks[c.get("peak", "bf16_flops_per_s")],
                     c["bytes"] / peaks["hbm_bytes_per_s"])
        measured += seconds
    if measured <= 0:
        return None
    return least / measured * 100.0
