"""Per-layer metrics of a traced run: each metric is a file
``perf/layer_metrics/<name>.json`` naming a reader kind
(``perf/readers/<kind>.py``) and its parameters; a reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations

import glob
import importlib
import json
import os

from . import harness, manifest
from .harness import BenchError, log

HERE = os.path.dirname(os.path.abspath(__file__))


def metrics(bench: dict, cell: dict, ctx: dict, extra_dirs=None) -> dict:
    out = {}
    for m in manifest.metrics_of(bench, "per_layer", cell["name"]):
        spec = manifest.load_layer_metric(m["name"], extra_dirs)
        reader = importlib.import_module(f"perf.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), ctx)
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def step_ops(bench: dict, cell: dict, extra_dirs=None) -> list:
    """The patterns of device-operation names that the cell's per-layer
    metrics ask to have timed inside each decode step (``params.ops``; a
    ``trace_step_roofline`` metric names its kernel so): what
    ``host_trace.reduce`` is told, so that a metric with a kernel of its
    own is a file and no edit there."""
    found = (manifest.load_layer_metric(m["name"], extra_dirs)
             .get("params", {}).get("ops")
             for m in manifest.metrics_of(bench, "per_layer", cell["name"]))
    return list(dict.fromkeys(p for p in found if p))


def reduce_trace(profile_dir: str, out_dir: str, extra_env: dict = None) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``profile_dir`` in a child of
    its own that is kept off the chip (``JAX_PLATFORMS=cpu``)."""
    traces = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)
    if not traces:
        raise BenchError(f"no .xplane.pb under {profile_dir}")
    out_path = os.path.join(out_dir, "trace_reduced.json")
    env = harness.child_env(dict(extra_env or {}, JAX_PLATFORMS="cpu"))
    harness.run_python_child(
        "trace_reduce", [os.path.join(HERE, "trace.py"), traces[-1], out_path],
        env, out_dir, 300)
    with open(out_path) as f:
        return json.load(f)


def breakdown(trace: dict, host: dict = None) -> dict:
    """The ten device operations that took most time, and the ten longest
    idle gaps by what the host was doing: each named by the step thread's
    innermost ``pst.*`` span over it (``unattributed`` under none), then by
    the device operations on either side (``host``: the reduction of
    ``perf/host_trace.py``; without it no gap can be named and none is
    given)."""
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = (host or {}).get("gaps", [])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[g["name"], g["seconds"]] for g in gaps]}
