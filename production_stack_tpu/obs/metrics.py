"""``pst_stage_duration_seconds`` — the per-stage latency decomposition.

One histogram, labeled by ``component`` (router | engine) and ``stage``
(the span taxonomy in docs/observability.md), fed by every span the
in-process recorder completes. Unlike the whole-request moving averages in
``router/stats/request_stats.py``, these are true distributions: a TTFT
regression decomposes into admission vs routing vs proxy vs engine queue
vs prefill in one PromQL query.

The histogram lives in its own :data:`OBS_REGISTRY` (not the process
default registry) because router and engine expose *different* registries
on ``/metrics`` — both handlers append :func:`render_obs_metrics` so the
stage surface shows up on either component without double registration.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Histogram,
    generate_latest,
)
from prometheus_client.openmetrics import exposition as _openmetrics

OBS_REGISTRY = CollectorRegistry()

# The OpenMetrics content type /metrics answers when the scraper
# negotiates it (Accept: application/openmetrics-text) — the format that
# carries exemplars. Plain Prometheus scrapes keep getting text/plain,
# byte-identical to the pre-exemplar exposition.
OPENMETRICS_CONTENT_TYPE = _openmetrics.CONTENT_TYPE_LATEST
_OM_EOF = b"# EOF\n"

# Buckets span sub-ms (routing decisions) to minutes (long decodes).
_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

stage_duration = Histogram(
    "pst_stage_duration_seconds",
    "Per-stage request latency decomposition (span durations by stage)",
    ["component", "stage"],
    registry=OBS_REGISTRY,
    buckets=_BUCKETS,
)


kv_integrity_failures = Counter(
    "pst_kv_integrity_failures",
    "KV pages whose BLAKE2 digest failed verification on a read path, by "
    "source (prefetch = disagg consumer manifest-following, match_prefix "
    "= the remote leg of prefix matching, restore = single-page fault-up)."
    " Each count is a quarantined replica copy and a failover/recompute — "
    "a corrupt page is never decoded (docs/kvserver.md)",
    ["source"],
    registry=OBS_REGISTRY,
)

kv_read_repairs = Counter(
    "pst_kv_read_repairs",
    "KV pages found on fewer than R ring owners during a read and "
    "re-pushed to the owners that missed (client-side read-repair, "
    "docs/kvserver.md)",
    registry=OBS_REGISTRY,
)

flight_snapshots_persisted = Counter(
    "pst_engine_flight_snapshots_persisted",
    "Flight-recorder snapshots written to --flight-snapshot-dir (bounded,"
    " oldest-first eviction) so tail-outlier post-mortems survive process"
    " death and restart (docs/observability.md \"Flight recorder\")",
    registry=OBS_REGISTRY,
)


def note_flight_snapshot_persisted(n: int = 1) -> None:
    if n > 0:
        flight_snapshots_persisted.inc(n)


def note_integrity_failure(source: str, n: int = 1) -> None:
    """Count ``n`` digest-verification failures on read path ``source``."""
    if n > 0:
        kv_integrity_failures.labels(source=source).inc(n)


def note_read_repair(n: int = 1) -> None:
    if n > 0:
        kv_read_repairs.inc(n)


def observe_stage(
    component: str, stage: str, seconds: float,
    trace_id: Optional[str] = None,
) -> None:
    """Record one stage duration (negative durations clamp to 0 so a
    misbehaving clock can never corrupt the histogram).

    ``trace_id`` attaches as an OpenMetrics exemplar on the bucket this
    observation lands in, so a Grafana p99 bucket links straight to the
    matching ``/debug/requests`` timeline. Exemplars surface only on
    negotiated OpenMetrics scrapes; plain exposition is unchanged.
    """
    child = stage_duration.labels(component=component, stage=stage)
    if trace_id:
        child.observe(max(seconds, 0.0), exemplar={"trace_id": trace_id})
    else:
        child.observe(max(seconds, 0.0))


def wants_openmetrics(accept: Optional[str]) -> bool:
    """Whether an Accept header negotiates the OpenMetrics exposition."""
    return "application/openmetrics-text" in (accept or "")


def render_registries(
    registries: Iterable[CollectorRegistry], accept: Optional[str] = None
) -> Tuple[bytes, str]:
    """Render several registries as one exposition body.

    Plain Prometheus (the default): the byte-for-byte concatenation the
    pre-exemplar handlers produced. With OpenMetrics negotiated, each
    registry renders through the OpenMetrics encoder (exemplars appear)
    and the per-registry ``# EOF`` terminators collapse to one.
    """
    regs = list(registries)
    if wants_openmetrics(accept):
        parts = [_openmetrics.generate_latest(r) for r in regs]
        body = b"".join(
            p[: -len(_OM_EOF)] if p.endswith(_OM_EOF) else p for p in parts
        ) + _OM_EOF
        return body, OPENMETRICS_CONTENT_TYPE
    return b"".join(generate_latest(r) for r in regs), "text/plain"


def render_obs_metrics() -> bytes:
    """Prometheus exposition of the shared observability registry —
    appended to both the router's and the engine's ``/metrics`` body."""
    return generate_latest(OBS_REGISTRY)
