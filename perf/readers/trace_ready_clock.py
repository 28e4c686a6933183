#!/usr/bin/env python3
"""What the engine's own clock for the device is worth, read off a capture:
the program stamps each launched program with the moment its fetch's poll
saw it ready (a zero-length ``pst.ready`` span: ``kind``, ``bucket``,
``service_us``, ``queued_us``, ``seen``; ``engine/runner.py`` ``_ReadyClock``)
and the capture has the program's own end in ``XLA Modules`` on the same
clock. params: ``stat``:

- ``lag_p95_ms``: the stamp less the end of the program it belongs to, 95th
  percentile over the capture, in milliseconds: how late the clock sees a
  program end;
- ``error_pct``: the sum of ``service_us`` over the stamps against the summed
  device time of the programs they belong to, as |difference| / programs x
  100: what the clock's totals (the busy counter, the billing meter) are
  off by. Single stamps err by a poll each way and telescope.

Joined by time, not by order: a ``pst.ready`` of kind k belongs to the
latest program of kind k (``host_trace.MODULE_KIND``) that ended at or
before it, each program to one stamp. None where the capture has no
``pst.ready`` (a program without the clock) or no such program.

    JAX_PLATFORMS=cpu python perf/readers/trace_ready_clock.py <trace.xplane.pb> <out.json>

reads the capture through ``host_trace.extract`` as it is; :func:`reduce` is
pure Python over what that returns and is tested on a recorded slice."""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness, host_trace  # noqa: E402
from perf.trace import DEVICE_PLANE  # noqa: E402

READY = "pst.ready"
# A poll reads the clock, then asks: a program that ends between the two is
# stamped a few microseconds before its end.
SLACK_NS = 20_000.0


def _quantile(values: list, q: float) -> float:
    values = sorted(values)
    at = q * (len(values) - 1)
    lo = int(at)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (at - lo)


def reduce(extracted: dict) -> dict:
    """-> ``stamps`` (``pst.ready`` spans in the capture), ``joined`` (those
    that found their program), ``late`` (joined stamps ``seen`` late),
    ``lag_ms`` {p50, p95, max} over the joined, ``service_s`` and
    ``module_s`` (the joined stamps' ``service_us`` summed, and their
    programs' device time), ``error_pct``, and the same two sums and the
    count ``by_kind`` and ``by_bucket`` (``<kind> <bucket>``)."""
    devices = [p for p in extracted["planes"] if DEVICE_PLANE.match(p["name"])]
    modules: dict = {}
    for line in (devices[0]["lines"] if devices else []):
        if line["name"] != host_trace.MODULES_LINE:
            continue
        for name, start, dur in line["events"]:
            kind = host_trace._module_kind(name)
            if kind is not None:
                modules.setdefault(kind, []).append((start + dur, dur))
    for ends in modules.values():
        ends.sort()
    stamps = sorted(
        (ev[1], ev[3]) for plane in extracted["planes"]
        if not DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"] for ev in line["events"] if ev[0] == READY)
    used: dict = {k: -1 for k in modules}
    lags, late = [], 0
    sums = {"all": [0, 0.0, 0.0]}
    by_kind: dict = {}
    by_bucket: dict = {}
    for at, stats in stamps:
        kind = stats.get("kind", "")
        ends = modules.get(kind)
        if not ends:
            continue
        i = bisect.bisect_right(ends, (at + SLACK_NS, float("inf"))) - 1
        if i < 0 or i <= used[kind]:
            continue  # its program ended before the capture began
        used[kind] = i
        end, dur = ends[i]
        lags.append((at - end) / 1e6)
        late += stats.get("seen") == "late"
        service = float(stats.get("service_us", 0)) / 1e6
        for table, key in ((sums, "all"), (by_kind, kind),
                           (by_bucket, f"{kind} {stats.get('bucket', '')}")):
            row = table.setdefault(key, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += service
            row[2] += dur / 1e9
    n, service_s, module_s = sums["all"]
    return {
        "stamps": len(stamps), "joined": n, "late": late,
        "lag_ms": ({"p50": _quantile(lags, 0.5), "p95": _quantile(lags, 0.95),
                    "max": max(lags)} if lags else {}),
        "service_s": service_s, "module_s": module_s,
        "error_pct": (abs(service_s - module_s) / module_s * 100.0
                      if module_s > 0 else None),
        "by_kind": by_kind, "by_bucket": by_bucket,
    }


def of_run(ctx: dict):
    """This run's reduction, made once in a child kept off the chip and
    kept in ``ctx``; None without a trace, or where the child fails."""
    if "ready_clock" not in ctx:
        ctx["ready_clock"] = _reduce_in_child(ctx) if ctx.get("trace") else None
    return ctx["ready_clock"]


def _reduce_in_child(ctx: dict):
    traces = sorted(glob.glob(os.path.join(ctx["out_dir"], "profile", "**",
                                           "*.xplane.pb"), recursive=True),
                    key=os.path.getmtime)
    out_path = os.path.join(ctx["out_dir"], "ready_clock_reduced.json")
    try:
        if not traces:
            raise harness.BenchError("no .xplane.pb under the run's profile/")
        harness.run_python_child(
            "ready_clock_reduce",
            [os.path.abspath(__file__), traces[-1], out_path],
            harness.child_env({"JAX_PLATFORMS": "cpu"}), ctx["out_dir"], 300)
    except harness.BenchError as e:
        harness.log(f"ready clock not read: {e}")
        return None
    with open(out_path) as f:
        reduced = json.load(f)
    harness.log(
        f"ready clock: {reduced['stamps']} pst.ready stamps, "
        f"{reduced['joined']} joined to their programs ({reduced['late']} "
        f"seen late), lag {reduced['lag_ms']}, service {reduced['service_s']:.4f} s "
        f"against {reduced['module_s']:.4f} s of programs; by kind "
        f"{reduced['by_kind']}")
    return reduced


def read(params: dict, ctx: dict):
    t = of_run(ctx)
    if not t or not t["joined"]:
        return None
    if params["stat"] == "lag_p95_ms":
        return t["lag_ms"]["p95"]
    if params["stat"] == "error_pct":
        return t["error_pct"]
    raise ValueError(f"trace_ready_clock: unknown stat {params['stat']!r}")


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[2], "w") as f:
        json.dump(reduce(host_trace.extract(argv[1])), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
