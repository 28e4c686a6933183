"""The share of the traced interval, in percent, in which the device was
idle while the engine's step thread was in one phase of its loop (the
innermost ``pst.<span>`` of ``perf/host_trace.py``). params: ``span``
(``wait``, ``launch``, ..., or ``unattributed``: idle inside a step but
under none of its phases, or under no span at all). The shares of all spans
add up to the device's idle share. None where the program wrote no such
spans; 0 where it did and the device never idled under this one."""

from perf import host_trace


def read(params: dict, ctx: dict):
    t = host_trace.of_run(ctx)
    if not t or not t["window_s"] or not t["idle_by_phase"]:
        return None
    return t["idle_by_phase"].get(params["span"], 0.0) / t["window_s"] * 100.0
