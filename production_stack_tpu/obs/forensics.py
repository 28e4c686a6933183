"""Tail-outlier forensics: turn a bad measured point into evidence.

A measured point can carry a p99 orders of magnitude over its median
and *nothing that explains it* (ROADMAP S2) — the flight recorder retains
the snapshot naming the stalled step's bucket and queue state, but no
path connected the measured outlier back to it. This module closes the
loop: whenever a measured bench point (or an e2e leg) crosses its tail
bar — ``p99 > factor × p50`` (the sweep's ``tail_outlier`` flag) or an
absolute SLO bar — the collector harvests an **evidence bundle**:

- the engine's flight-recorder dump with retained + persisted snapshots
  (``GET /debug/flight?snapshots=1``) and its ``/debug/state``;
- the ``/debug/requests`` timelines for the worst trace ids (by
  duration) on engine and router;
- the router's gossip-merged ``GET /debug/fleet`` snapshot;
- before/after ``/metrics`` deltas (``mark()`` before measuring, delta
  at collection);
- any snapshots a dead engine persisted to ``--flight-snapshot-dir``
  (the post-mortem path — collectable after SIGKILL).

Bundles are written as JSON beside the bench output
(``<out>.evidence/point_<phase>_<point>.json``), and counted by
``pst_forensics_bundles_total{trigger}``.

Deliberately stdlib-only on the collection path (urllib, no aiohttp):
``bench.py`` imports this before any server dependency is guaranteed,
and every fetch is best-effort — a half-dead stack yields a bundle with
``error`` entries, never an exception that kills the bench run.
"""

from __future__ import annotations

import json
import os
import re
import time
import urllib.request
from typing import Dict, Iterable, List, Optional

from .flight import load_snapshot_dir

BUNDLE_SCHEMA = "pst-evidence-bundle/v1"
DEFAULT_TAIL_FACTOR = 3.0


def crosses_tail_bar(
    p50_ms: Optional[float],
    p99_ms: Optional[float],
    factor: float = DEFAULT_TAIL_FACTOR,
    abs_bar_ms: Optional[float] = None,
) -> Optional[str]:
    """The trigger name when (p50, p99) crosses a tail bar, else None.

    ``tail_outlier`` is the sweep's own flag (p99 worse than ``factor`` ×
    p50 — an unexplained tail); ``slo_bar`` is an absolute p99 bar for
    legs with an SLO target instead of a self-relative shape."""
    if p99_ms is None:
        return None
    if abs_bar_ms is not None and p99_ms > abs_bar_ms:
        return "slo_bar"
    if p50_ms is not None and p50_ms > 0 and p99_ms > factor * p50_ms:
        return "tail_outlier"
    return None


def _fetch_json(url: str, timeout: float) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def fetch_metrics(url: str, timeout: float = 5.0) -> Dict[str, float]:
    """One ``/metrics`` scrape parsed to ``{series_key: value}``.

    The key is the full sample line head (name + label set), so deltas
    are per-series — a counter moving on one engine is attributable."""
    with urllib.request.urlopen(f"{url}/metrics", timeout=timeout) as r:
        text = r.read().decode()
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            key, val = line.rsplit(" ", 1)
            out[key.strip()] = float(val)
        except ValueError:
            continue
    return out


def metrics_delta(
    before: Dict[str, float], after: Dict[str, float]
) -> Dict[str, float]:
    """Per-series after−before for series that moved (or appeared).

    A series absent from ``before`` (first observation after the mark)
    delta-counts its full value — new label children born during the
    measured window are part of what happened in it."""
    out: Dict[str, float] = {}
    for key, val in after.items():
        d = val - before.get(key, 0.0)
        if d != 0.0:
            out[key] = round(d, 6)
    return out


def worst_traces(requests_payload: dict, n: int = 3) -> List[dict]:
    """The ``n`` slowest request timelines from a ``/debug/requests``
    body (most evidence per byte: the traces that ARE the tail)."""
    reqs = requests_payload.get("requests") or []
    reqs = [r for r in reqs if isinstance(r, dict)]
    reqs.sort(key=lambda r: r.get("duration_ms") or 0.0, reverse=True)
    return reqs[:n]


def _point_slug(phase: str, point) -> str:
    raw = f"{phase}_{point}"
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", raw)


class ForensicsCollector:
    """Harvests evidence bundles into ``<out>.evidence/``.

    Lifecycle per measured leg: ``mark(urls)`` before the load starts
    (captures the /metrics baseline), measure, then ``maybe_collect``
    with the leg's p50/p99 — a crossed bar harvests and writes the
    bundle; a healthy leg costs one dict comparison."""

    def __init__(
        self,
        evidence_dir: str,
        tail_factor: float = DEFAULT_TAIL_FACTOR,
        timeout_s: float = 5.0,
    ):
        self.evidence_dir = evidence_dir
        self.tail_factor = float(tail_factor)
        self.timeout_s = float(timeout_s)
        self.bundles: List[str] = []

    # -- metrics baseline -------------------------------------------------

    def mark(self, urls: Iterable[str]) -> Dict[str, Dict[str, float]]:
        """Best-effort /metrics baseline for each URL (missing scrapes
        record an empty dict: the delta then shows absolute values)."""
        baseline: Dict[str, Dict[str, float]] = {}
        for url in urls:
            try:
                baseline[url] = fetch_metrics(url, self.timeout_s)
            except Exception:  # noqa: BLE001 — evidence is best-effort
                baseline[url] = {}
        return baseline

    # -- collection -------------------------------------------------------

    def maybe_collect(
        self,
        phase: str,
        point,
        p50_ms: Optional[float],
        p99_ms: Optional[float],
        *,
        abs_bar_ms: Optional[float] = None,
        engines: Iterable[str] = (),
        router: Optional[str] = None,
        snapshot_dirs: Iterable[str] = (),
        baseline: Optional[Dict[str, Dict[str, float]]] = None,
        detail: Optional[dict] = None,
    ) -> Optional[str]:
        trigger = crosses_tail_bar(
            p50_ms, p99_ms, self.tail_factor, abs_bar_ms
        )
        if trigger is None:
            return None
        full_detail = {
            "p50_ms": p50_ms,
            "p99_ms": p99_ms,
            "tail_factor": self.tail_factor,
            "abs_bar_ms": abs_bar_ms,
            **(detail or {}),
        }
        return self.collect(
            trigger, phase, point,
            engines=engines, router=router, snapshot_dirs=snapshot_dirs,
            baseline=baseline, detail=full_detail,
        )

    def collect(
        self,
        trigger: str,
        phase: str,
        point,
        *,
        engines: Iterable[str] = (),
        router: Optional[str] = None,
        snapshot_dirs: Iterable[str] = (),
        baseline: Optional[Dict[str, Dict[str, float]]] = None,
        detail: Optional[dict] = None,
        worst_n: int = 3,
    ) -> str:
        """Harvest one bundle NOW and write it; returns the file path.

        Every fetch is individually guarded: a dead engine contributes
        ``{"error": ...}`` plus whatever its snapshot dir retained."""
        t = self.timeout_s
        bundle: dict = {
            "schema": BUNDLE_SCHEMA,
            "trigger": trigger,
            "phase": phase,
            "point": point,
            "ts": time.time(),
            "detail": detail or {},
            "engines": {},
            "router": None,
            "metrics_delta": {},
            "postmortem_snapshots": [],
        }
        for url in engines:
            entry: dict = {}
            try:
                entry["flight"] = _fetch_json(
                    f"{url}/debug/flight?snapshots=1", t
                )
            except Exception as e:  # noqa: BLE001
                entry["flight"] = {"error": str(e)}
            try:
                entry["state"] = _fetch_json(f"{url}/debug/state", t)
            except Exception as e:  # noqa: BLE001
                entry["state"] = {"error": str(e)}
            try:
                entry["worst_traces"] = worst_traces(
                    _fetch_json(f"{url}/debug/requests?limit=100", t),
                    worst_n,
                )
            except Exception as e:  # noqa: BLE001
                entry["worst_traces"] = [{"error": str(e)}]
            bundle["engines"][url] = entry
        if router:
            rentry: dict = {"url": router}
            try:
                rentry["fleet"] = _fetch_json(f"{router}/debug/fleet", t)
            except Exception as e:  # noqa: BLE001
                rentry["fleet"] = {"error": str(e)}
            try:
                rentry["worst_traces"] = worst_traces(
                    _fetch_json(f"{router}/debug/requests?limit=100", t),
                    worst_n,
                )
            except Exception as e:  # noqa: BLE001
                rentry["worst_traces"] = [{"error": str(e)}]
            bundle["router"] = rentry
        for url in (baseline or {}):
            try:
                bundle["metrics_delta"][url] = metrics_delta(
                    baseline[url], fetch_metrics(url, t)
                )
            except Exception as e:  # noqa: BLE001
                bundle["metrics_delta"][url] = {"error": str(e)}
        for d in snapshot_dirs:
            bundle["postmortem_snapshots"].extend(load_snapshot_dir(d))
        path = self._write(bundle, phase, point)
        try:
            from .metrics import note_forensics_bundle

            note_forensics_bundle(trigger)
        except Exception:  # noqa: BLE001 — metrics must not kill harvest
            pass
        return path

    def collect_postmortem(
        self,
        phase: str,
        point,
        snapshot_dirs: Iterable[str],
        detail: Optional[dict] = None,
    ) -> Optional[str]:
        """The after-death path: no live endpoints, only what the engine
        persisted to ``--flight-snapshot-dir`` before it was killed.
        Returns None (no bundle) when the dirs hold nothing — an empty
        post-mortem is noise, not evidence."""
        snaps: List[dict] = []
        for d in snapshot_dirs:
            snaps.extend(load_snapshot_dir(d))
        if not snaps:
            return None
        bundle = {
            "schema": BUNDLE_SCHEMA,
            "trigger": "postmortem",
            "phase": phase,
            "point": point,
            "ts": time.time(),
            "detail": detail or {},
            "engines": {},
            "router": None,
            "metrics_delta": {},
            "postmortem_snapshots": snaps,
        }
        path = self._write(bundle, phase, point)
        try:
            from .metrics import note_forensics_bundle

            note_forensics_bundle("postmortem")
        except Exception:  # noqa: BLE001
            pass
        return path

    def _write(self, bundle: dict, phase: str, point) -> str:
        os.makedirs(self.evidence_dir, exist_ok=True)
        path = os.path.join(
            self.evidence_dir, f"point_{_point_slug(phase, point)}.json"
        )
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(bundle, f)
        os.replace(tmp, path)
        self.bundles.append(path)
        return path


def evidence_dir_for(out_path: Optional[str]) -> str:
    """``<out>.evidence`` beside the bench output ($PST_BENCH_OUT when
    set, a /tmp default otherwise — the bundles must land somewhere even
    when the driver never asked for a file mirror)."""
    base = out_path or "/tmp/pst_bench"
    return base + ".evidence"
