#!/usr/bin/env python3
"""The step loop's phases on the device trace's clock: what the host was
doing while the device idled (in sum, and for each of the longest idle
stretches), the time of each step program, and each decode step's context
lengths beside the time that the device operations a per-layer metric names
took inside it.

    JAX_PLATFORMS=cpu python perf/host_trace.py <trace.xplane.pb> <out.json> [<ops pattern> ...]

Beside ``trace.py`` and in the same two steps:
:func:`extract` reads the ``.xplane.pb`` into plain lists, :func:`reduce` is
pure Python over those lists and is tested on a recorded slice.

What it reads (the program's side is ``obs/engine_telemetry.py``
``phase``): the engine's step thread writes ``pst.<phase>`` spans into the
profiler's own trace with ``jax.profiler.TraceAnnotation``: ``pst.no_work``,
``pst.intake`` and ``pst.step`` side by side, and inside a step
``pst.schedule``, ``pst.batch_build``, ``pst.launch``, ``pst.wait`` and
``pst.postprocess``, plus a zero-length ``pst.step_info`` whose stats say
what the step was (``kind``, ``bucket``, ``rows``, ``new_tokens``,
``kv_tokens``, ``kv_pages``). They are events of a ``/host:CPU`` line and
share the clock of the ``/device:TPU:<n>`` planes. The step programs are
named ``jit_pst_decode_step``, ``jit_pst_prefill_step``,
``jit_pst_decode_burst`` and ``jit_pst_spec_verify`` in ``XLA Modules``.
A trace of a program that writes none of this reduces to empty tables, and
the readers then leave their metrics out.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness  # noqa: E402
from perf.trace import DEVICE_PLANE, OPS_LINE, _union, short_name  # noqa: E402

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "pst."
STEP, INFO, LAUNCH, WAIT = "pst.step", "pst.step_info", "pst.launch", "pst.wait"
UNATTRIBUTED = "unattributed"
GAPS_KEPT = 40  # kinds of idle stretch the reduction keeps, longest first
# which step kind launches which program
MODULE_KIND = (("jit_pst_decode", "decode"), ("jit_pst_prefill", "prefill"),
               ("jit_pst_spec", "spec_verify"))


def extract(path: str) -> dict:
    """Device planes: the ``XLA Ops`` and ``XLA Modules`` events and the
    plane's ``interval`` (first to last event of any of its lines, which is
    how ``trace.py`` bounds the traced interval). Host planes: only the
    ``pst.*`` events, each with its stats, line by line."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines, lo, hi = [], float("inf"), float("-inf")
        for line in plane.lines:
            if device:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
                for _, s, d in events:
                    lo, hi = min(lo, s), max(hi, s + d)
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
            else:
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           {k: v for k, v in e.stats}]
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append(dict({"name": plane.name, "lines": lines},
                               **({"interval": [lo, hi]} if device else {})))
    return {"planes": planes}


def _complement(busy: list, lo: float, hi: float) -> list:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, min(s, hi)])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return [[s, e] for s, e in out if e > s]


def leaf_segments(spans: list) -> list:
    """[(start, end, name)] covering every moment that lies inside a span
    of one thread, named by the innermost span there. ``spans`` are
    (start, end, name) and nest (one thread's annotations do)."""
    segs, stack, at = [], [], None

    def close(upto):
        nonlocal at
        while stack and stack[-1][1] <= upto:
            _, end, name = stack.pop()
            if end > at:
                segs.append((at, end, name))
                at = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
            if s > at:
                segs.append((at, s, stack[-1][2]))
        at = s
        stack.append((s, e, name))
    close(float("inf"))
    return segs


def _idle_pieces(ops: list, idle: list, segs: list) -> list:
    """[(span, around, seconds)] for every idle stretch of one device, cut
    where the step thread's innermost span changes: that span
    (``pst.wait``; ``unattributed`` under no span, or inside ``pst.step``
    but under none of its phases), and the device operations on either side
    of the whole stretch. ``idle`` and ``segs`` are sorted."""
    ran = [(text, s, s + d) for text, s, d in ops if d > 0]
    by_start = sorted(ran, key=lambda op: op[1])
    by_end = sorted(ran, key=lambda op: op[2])
    starts, ends = [op[1] for op in by_start], [op[2] for op in by_end]
    out, j = [], 0
    for s, e in idle:
        i = bisect.bisect_right(ends, s + 1) - 1
        n = bisect.bisect_left(starts, e - 1)
        before = short_name(by_end[i][0]) if i >= 0 else "start of trace"
        after = short_name(by_start[n][0]) if n < len(starts) else "end of trace"
        around = f"after {before} / before {after}"[:180]
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        at, k = s, j
        while k < len(segs) and segs[k][0] < e:
            a, b = max(s, segs[k][0]), min(e, segs[k][1])
            if a > at:
                out.append((UNATTRIBUTED, around, (a - at) / 1e9))
            span = UNATTRIBUTED if segs[k][2] == STEP else segs[k][2]
            out.append((span, around, (b - a) / 1e9))
            at, k = b, k + 1
        if e > at:
            out.append((UNATTRIBUTED, around, (e - at) / 1e9))
    return out


def _step_thread(extracted: dict) -> list:
    """The ``pst.*`` events of the line that carries the ``pst.step``
    spans: other threads (an embedding request's fetch) may write spans of
    their own, and they say nothing of the step loop."""
    best = []
    for plane in extracted["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            n = sum(1 for ev in line["events"] if ev[0] == STEP)
            if n > sum(1 for ev in best if ev[0] == STEP):
                best = line["events"]
    return best


def _module_kind(name: str):
    return next((k for prefix, k in MODULE_KIND if name.startswith(prefix)), None)


def _steps(events: list, lo: float, hi: float) -> list:
    """One record per ``pst.step`` that lies wholly inside [lo, hi]: the
    stats of its ``pst.step_info`` and its launches and waits, in order."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    steps = []
    for ev in order:
        name, s, d = ev[0], ev[1], ev[2]
        if name == STEP:
            steps.append({"start": s, "end": s + d, "info": [], "launch": [],
                          "wait": []})
        elif steps and s + d <= steps[-1]["end"]:
            if name == INFO:
                steps[-1]["info"].append(ev[3])
            elif name in (LAUNCH, WAIT):
                steps[-1][name[len(SPAN_PREFIX):]].append(
                    [s, s + d, ev[3].get("kind", ""), ev[3].get("pipelined", 0)])
    return [st for st in steps if st["start"] >= lo and st["end"] <= hi]


def reduce(extracted: dict, step_ops: tuple = ()) -> dict:
    """``step_ops``: patterns over a device operation's text, each asked for by a
    per-layer metric (``params.ops`` of a ``trace_step_roofline`` metric).
    -> ``window_s`` and ``idle_s`` (as ``trace.py`` has them, averaged
    over the device planes), ``spans`` (how many ``pst.*`` events the step
    thread wrote), ``idle_by_phase`` {phase: seconds of device idle while
    that was the step thread's innermost span; idle inside ``pst.step`` but
    under none of its phases, or under no span at all, is
    ``"unattributed"``}, ``gaps`` (the idle stretches cut at the step
    thread's span boundaries, the same kind many times over summed into one
    entry ``{"name", "seconds", "count"}`` over all device planes, longest
    first: see :func:`_idle_pieces`), ``modules`` {program name without its id: [count,
    seconds]}, ``decode_steps`` (per decode step wholly inside the traced
    interval and joined to the module it launched: its stats, ``module_s``,
    ``ops_s`` = {pattern: time of the operations inside that module whose
    text the pattern finds} for each of ``step_ops``), ``steps_kept`` and
    ``clock_violations`` (programs that start before their ``pst.launch``
    opens or, where the step fetches what it launched, end after its
    ``pst.wait`` closes: host and device clocks that disagree, or a join
    that went wrong)."""
    devices = [p for p in extracted["planes"] if DEVICE_PLANE.match(p["name"])]
    thread = _step_thread(extracted)
    spans = [(ev[1], ev[1] + ev[2], ev[0]) for ev in thread if ev[0] != INFO]
    segs = leaf_segments(spans)
    lo = min((p["interval"][0] for p in devices), default=0.0)
    hi = max((p["interval"][1] for p in devices), default=0.0)
    idle_by_phase, idle_s, modules, gaps = {UNATTRIBUTED: 0.0}, 0.0, {}, {}
    first_modules, first_ops = [], []
    for plane in devices:
        by_line = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = by_line.get(OPS_LINE, [])
        idle = _complement(
            _union([[s, s + d] for _, s, d in ops if d > 0]), lo, hi)
        idle_s += sum(e - s for s, e in idle) / 1e9 / len(devices)
        for span, around, sec in _idle_pieces(ops, idle, segs):
            phase = span[len(SPAN_PREFIX):] if span != UNATTRIBUTED else span
            idle_by_phase[phase] = idle_by_phase.get(phase, 0.0) + sec / len(devices)
            g = gaps.setdefault((span, around), {
                "name": f"{span}: {around}", "seconds": 0.0, "count": 0})
            g["seconds"] += sec
            g["count"] += 1
        for name, s, d in by_line.get(MODULES_LINE, []):
            m = modules.setdefault(name.split("(")[0], [0, 0.0])
            m[0] += 1
            m[1] += d / 1e9
        if plane is devices[0]:
            first_modules = sorted(by_line.get(MODULES_LINE, []), key=lambda e: e[1])
            first_ops = ops

    # Steps against the programs they launched, on the first device: the
    # device runs programs in the order they were launched, so within one
    # kind the n-th launch is the n-th module, once the modules launched
    # before the first whole step are set aside.
    steps = _steps(thread, lo, hi)
    patterns = [(p, re.compile(p)) for p in dict.fromkeys(step_ops)]
    decode_steps, violations = [], 0
    for kind in {k for _, k in MODULE_KIND}:
        launches = [(st, ln) for st in steps for ln in st["launch"] if ln[2] == kind]
        mods = [m for m in first_modules if _module_kind(m[0]) == kind]
        if not launches:
            continue
        mods = [m for m in mods if m[1] + m[2] > launches[0][1][0]]
        for (st, ln), (name, ms, md) in zip(launches, mods):
            # a pipelined launch is fetched by a later step: the wait that
            # follows it in its own step is for the program before it
            waits = [] if ln[3] else [
                w for w in st["wait"] if w[2] == kind and w[0] >= ln[0]]
            if ms < ln[0] or (waits and ms + md > waits[0][1]):
                violations += 1
            info = next((i for i in st["info"] if i.get("kind") == kind), None)
            if kind != "decode" or info is None or len(st["launch"]) != 1:
                continue
            inside = [(text, d) for text, s, d in first_ops
                      if ms <= s and s + d <= ms + md]
            decode_steps.append(dict(
                info, module=name.split("(")[0], module_s=md / 1e9,
                ops_s={p: sum(d for text, d in inside if pat.search(text)) / 1e9
                       for p, pat in patterns}))
    return {
        "window_s": (hi - lo) / 1e9 if devices and hi > lo else 0.0,
        "idle_s": idle_s,
        "spans": len(thread),
        "idle_by_phase": idle_by_phase if thread else {},
        "gaps": sorted(gaps.values(), key=lambda g: -g["seconds"])[:GAPS_KEPT],
        "modules": modules,
        "steps_kept": len(steps),
        "decode_steps": decode_steps,
        "clock_violations": violations,
    }


def of_run(ctx: dict):
    """The reduction of this run's trace, made once and kept in ``ctx``:
    a child of its own off the chip, as ``layers.reduce_trace`` runs
    ``trace.py``, told the patterns of ``ctx["step_ops"]``
    (``layers.step_ops``: what the cell's metrics ask to have timed inside
    each decode step). None where the run has no trace or the child fails
    (the readers then leave their metrics out, and the log says why)."""
    if "host_trace" not in ctx:
        ctx["host_trace"] = _reduce_in_child(ctx) if ctx.get("trace") else None
    return ctx["host_trace"]


def _reduce_in_child(ctx: dict):
    traces = sorted(glob.glob(os.path.join(ctx["out_dir"], "profile", "**",
                                           "*.xplane.pb"), recursive=True),
                    key=os.path.getmtime)
    out_path = os.path.join(ctx["out_dir"], "host_trace_reduced.json")
    try:
        if not traces:
            raise harness.BenchError("no .xplane.pb under the run's profile/")
        harness.run_python_child(
            "host_trace_reduce",
            [os.path.abspath(__file__), traces[-1], out_path,
             *ctx.get("step_ops", [])],
            harness.child_env({"JAX_PLATFORMS": "cpu"}), ctx["out_dir"], 300)
    except harness.BenchError as e:
        harness.log(f"host trace not reduced: {e}")
        return None
    with open(out_path) as f:
        reduced = json.load(f)
    harness.log(f"host trace: {reduced['spans']} pst.* spans, "
                f"{reduced['steps_kept']} whole steps, "
                f"{len(reduced['decode_steps'])} decode steps joined to their "
                f"programs, clock violations {reduced['clock_violations']}")
    return reduced


def main(argv) -> int:
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    extracted = extract(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(extracted, tuple(argv[3:])), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
