#!/usr/bin/env python3
"""From a profiler trace to numbers: device busy and idle time, time per
device operation, and the calls a roofline reader needs. (The idle
stretches, each under what the host was doing, are ``host_trace.py``'s.)

    JAX_PLATFORMS=cpu python perf/trace.py <trace.xplane.pb> <out.json>

Two steps, kept apart so that the arithmetic can be tested on a small
recorded trace without jax: :func:`extract` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` into plain lists; :func:`reduce` is pure
Python over those lists.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<id>)``), ``XLA Ops`` and ``Async
XLA Ops``; host threads are lines of ``/host:CPU``. An event of ``XLA Ops``
is named by the whole text of its HLO instruction, operand shapes included
(``%int4_matmul.75 = f32[16,14336]{...} custom-call(bf16[16,2048]{...} ...``),
which is where the cost functions take a call's shapes from. A Pallas kernel
carries its Python function's name (``int4_matmul``) unless it is traced
through a closed call (the attention kernels are ``%closed_call.<n>``); the
program gives no kernel or jitted step a stable name of its own yet. A
capture in which no program ran has no ``/device:TPU`` plane at all.
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_RESULT = re.compile(r"^(\S+) = \(?([a-z0-9]+\[[\d,]*\])")


def short_name(text: str) -> str:
    """``%int4_matmul.75 f32[16,14336]`` from the instruction's text: its
    name and result shape, which tell apart the same instruction name in
    two programs."""
    m = _RESULT.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text.split(" = ")[0][:120]
OPS_LINE = "XLA Ops"


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> list:
    """(name, self_ns) per event of one line: an event's duration
    minus what the events nested inside it cover (a ``while`` contains its
    body's operations on the same line)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [events[i][2] for i in range(len(events))]
    stack = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1] + 1e-6:
            self_ns[stack[-1][0]] -= events[i][2]
        stack.append((i, e))
    return [(events[i][0], max(self_ns[i], 0.0)) for i in range(len(events))]


def reduce(extracted: dict) -> dict:
    """-> ``busy_s`` and ``window_s`` (busy averaged over the device
    planes; the window is the traced interval as the devices saw it, first
    to last event of any device plane: the host's lines run on for a few
    tenths of a second after the device tracer has stopped), ``ops`` {name:
    self seconds, summed over chips} and ``calls`` (one entry per distinct
    instruction text, with its short name, count and self seconds)."""
    devices = [p for p in extracted["planes"] if DEVICE_PLANE.match(p["name"])]
    lo, hi = float("inf"), float("-inf")
    for plane in devices:
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                lo, hi = min(lo, s), max(hi, s + d)
    busy, ops, calls = [], {}, {}
    for plane in devices:
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE] or [
            ln for ln in plane["lines"] if ln["name"] not in ("Steps", "XLA Modules")]
        events = [e for ln in lines for e in ln["events"]]
        merged = _union([[s, s + d] for _, s, d in events if d > 0])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for ln in lines:
            for text, self_ns in _self_times(ln["events"]):
                name = short_name(text)
                ops[name] = ops.get(name, 0.0) + self_ns / 1e9
                c = calls.setdefault(text, {"name": name, "text": text[:4000],
                                            "count": 0, "seconds": 0.0})
                c["count"] += 1
                c["seconds"] += self_ns / 1e9
    return {
        "window_s": (hi - lo) / 1e9 if devices and hi > lo else 0.0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "device_planes": [p["name"] for p in devices],
        "ops": ops,
        "calls": sorted(calls.values(), key=lambda c: -c["seconds"]),
    }


def describe(extracted: dict) -> dict:
    """Planes, lines, event counts and a sample event each: for a look by
    hand."""
    out = {}
    for plane in extracted["planes"]:
        for line in plane["lines"]:
            ev = line["events"]
            out[f"{plane['name']} | {line['name']}"] = {
                "events": len(ev), "sample": ev[len(ev) // 2] if ev else None}
    return out


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    extracted = extract(argv[1])
    reduced = reduce(extracted)
    if not reduced["device_planes"] or reduced["busy_s"] <= 0:
        print(f"perf/trace.py: no operation ran on a device in {argv[1]} "
              f"(planes: {[p['name'] for p in extracted['planes']]})", file=sys.stderr)
        return 1
    with open(argv[2] + ".lines.json", "w") as f:
        json.dump(describe(extracted), f, indent=1)
    with open(argv[2], "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
