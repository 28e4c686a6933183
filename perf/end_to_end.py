"""The end-to-end metrics, from the client's records of the window. Each
is taken over all the work and all the time of the window; a tail is the
tail of all requests that were due in it."""

from __future__ import annotations

from . import manifest
from .client import percentile
from .harness import BenchError, log


def _ttft(q):
    def f(ctx):
        v = ctx["summary"]["ttft_ms"]
        if not v:
            raise BenchError("no request of the window produced a token")
        return percentile(v, q)
    return f


def _itl(q):
    def f(ctx):
        v = ctx["summary"]["gap_ms"]
        if not v:
            raise BenchError("no inter-token gap was observed in the window")
        return percentile(v, q)
    return f


def _out_tok_per_s(ctx):
    """Every output token that arrived inside the window, whether or not
    its request completed before the close, per second and chip: all the
    work and all the time of the window, and no edge effect from the
    requests that straddle the close."""
    chips = ctx["cell"]["chips"]
    return ctx["summary"]["output_tokens_streamed"] / ctx["seconds"] / chips


COMPUTE = {
    "ttft_p50_ms": _ttft(50),
    "ttft_p95_ms": _ttft(95),
    "itl_p50_ms": _itl(50),
    "itl_p95_ms": _itl(95),
    "out_tok_per_s": _out_tok_per_s,
    "setup_s": lambda ctx: ctx["setup_s"],
}


def metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    out = {}
    for m in manifest.metrics_of(bench, "end_to_end", cell["name"]):
        out[m["name"]] = {"value": COMPUTE[m["name"]](ctx), "unit": m["unit"]}
    return out


def log_side_numbers(summary: dict, seconds: float) -> None:
    """Sample counts and medians of what is not a metric, on earlier lines."""
    t, g, late = (summary["ttft_ms"], summary["gap_ms"],
                  summary["generator_late_ms"])
    if t:
        log(f"ttft: n={len(t)} p50={percentile(t, 50):.1f} ms "
            f"p95={percentile(t, 95):.1f} ms max={max(t):.1f} ms; share <= 200 ms "
            f"{sum(1 for x in t if x <= 200) / len(t):.3f}")
    if g:
        log(f"gaps: n={len(g)} p50={percentile(g, 50):.2f} ms "
            f"p95={percentile(g, 95):.2f} ms max={max(g):.1f} ms")
    if late:
        log(f"generator lateness: p50={percentile(late, 50):.2f} ms "
            f"max={max(late):.2f} ms")
    log(f"completed {summary['completed']} requests "
        f"({summary['completed'] / seconds:.2f}/s), "
        f"{summary['output_tokens_completed'] / seconds:.1f} output tok/s in "
        f"completed requests, {summary['output_tokens_streamed'] / seconds:.1f} "
        "streamed")
