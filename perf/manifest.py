"""``BENCHMARK.json`` and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of
its own, found by name; this module only joins names to paths."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
            return dict(w, config_file=cfg["file"])
    raise SystemExit(
        f"unknown workload {workload!r}; BENCHMARK.json has "
        f"{[w['name'] for w in bench['workloads']]}")


def _find(kind: str, name: str, extra_dirs) -> str:
    """``<name>.json`` in the benchmark's ``perf/<kind>/`` directory, or in
    one of ``extra_dirs`` (the tests keep their tiny files apart)."""
    for d in list(extra_dirs or []) + [os.path.join(HERE, kind)]:
        path = os.path.join(d, f"{name}.json")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind} file {name}.json (looked in perf/{kind}"
                            f"{' and ' + str(list(extra_dirs)) if extra_dirs else ''})")


def load_mix(traffic: str, extra_dirs=None) -> dict:
    with open(_find("traffic", traffic, extra_dirs)) as f:
        return json.load(f)


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def load_layer_metric(name: str, extra_dirs=None) -> dict:
    with open(_find("layer_metrics", name, extra_dirs)) as f:
        return json.load(f)


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that the
    cell reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]
