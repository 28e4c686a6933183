"""A whole decode step's share of its roofline, in percent: per decode step
that lies wholly inside the traced interval and was joined to the program
it launched (``perf/host_trace.py``'s ``decode_steps``), the cost function
gives the operations and bytes the step has to do from the step's own
metadata (``pst.step_info``), the least time is the larger of operations /
peak and bytes / bandwidth, and the measured time is ``module_s``, the
device time of that program from its first operation to its last. The
share is the sum of the least times over the sum of the measured.

``trace_step_roofline`` cannot give this: it times the operations a
pattern finds inside the program, and a pattern over every operation would
count a ``%while`` and the operations inside it twice. params: ``cost``
(module under ``perf/cost``; handed the step's fields whole, ``module_s``
among them). A step the cost function cannot cost makes the metric absent,
not guessed."""

from perf import cost as costs
from perf import host_trace


def read(params: dict, ctx: dict):
    t, peaks = host_trace.of_run(ctx), ctx.get("peaks")
    if not t or not peaks:
        return None
    cost = costs.load(params["cost"], ctx.get("cost_dirs"))
    least = measured = 0.0
    for step in t["decode_steps"]:
        seconds = step.get("module_s") or 0.0
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
