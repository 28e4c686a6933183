"""A percentile of what the benchmark's own client saw in the window.
params: ``series`` (``ttft_ms`` | ``gap_ms`` | ``generator_late_ms``),
``q`` (0..100). In a saturated cell these are layer numbers: the queue
grows or the loop is closed, so the tails say how the load was shaped, and
the end-to-end metric is the rate."""

from perf.client import percentile


def read(params: dict, ctx: dict):
    values = ctx["summary"].get(params["series"])
    if not values:
        return None
    return percentile(values, float(params["q"]))
