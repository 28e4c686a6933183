"""The single source of truth for every ``pst`` metric name.

Dashboards (observability/gen_dashboards.py), alert rules
(prometheus-rules.yaml), docs/observability.md and operators' PromQL all
key on these names; before this module they were re-listed in each
consumer and drift was caught (at best) by a regex scan. Now: code that
constructs a ``pst``-prefixed Counter/Gauge/Histogram must have a
matching :class:`MetricSpec` here — the ``metric-registry`` pstlint
check enforces both directions (undeclared constructor -> finding; stale
declaration -> finding) plus docs coverage, and
``scripts/check_metric_docs.py`` is a thin CI shim over the same logic.

Kept importable with zero third-party dependencies (no prometheus_client
import) so the analyzer and scripts can consume it on a bare checkout.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One declared metric family.

    ``name`` is the constructor name (what ``Counter(...)`` receives —
    prometheus_client appends ``_total`` to counters at exposition);
    ``module`` is the declaring module, for doc pointers.
    """

    name: str
    kind: str
    module: str

    @property
    def exposition_name(self) -> str:
        if self.kind == COUNTER and not self.name.endswith("_total"):
            return self.name + "_total"
        return self.name


# Declaration order groups by owning module (matches the metric rows in
# docs/observability.md).
REGISTRY: Tuple[MetricSpec, ...] = (
    # --- obs/metrics.py: shared stage-latency decomposition -------------
    MetricSpec("pst_stage_duration_seconds", HISTOGRAM, "obs/metrics.py"),
    # Replicated remote-KV tier integrity (docs/kvserver.md): corrupt
    # replica copies detected on read (by source path) and blocks
    # re-pushed to owners that missed them (read-repair).
    MetricSpec("pst_kv_integrity_failures", COUNTER, "obs/metrics.py"),
    MetricSpec("pst_kv_read_repairs", COUNTER, "obs/metrics.py"),
    # Flight snapshots persisted to disk so they survive process death
    # (docs/observability.md "Flight recorder").
    MetricSpec("pst_engine_flight_snapshots_persisted", COUNTER, "obs/metrics.py"),
    # --- obs/logging.py: structured-logging hot-path sampler ------------
    MetricSpec("pst_log_dropped", COUNTER, "obs/logging.py"),
    # --- obs/engine_telemetry.py: TPU engine device layer ---------------
    MetricSpec("pst_engine_compile", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_compile_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_step_duration_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_host_gap_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_step_phase_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    # What held a step off (docs/observability.md "Flight recorder"): the
    # step thread's off-CPU time, stalled cycles by cause, collections.
    MetricSpec("pst_engine_step_offcpu_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_stalls", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_stall_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_gc_pause_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_batch_fill_ratio", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_tokens_per_second", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_kv_page_occupancy", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_kv_page_high_watermark", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_preemptions", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_swap_out", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_swap_in", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_start_time_seconds", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_startup_seconds", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_warmup_coverage", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_warmup_buckets", GAUGE, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_compile_cache_hits", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_compile_cache_misses", COUNTER, "obs/engine_telemetry.py"),
    # The program store (engine/program_store.py): what became of a step
    # shape's first use, and what first uses spent their time on.
    MetricSpec("pst_engine_program_store", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_program_first_use_seconds", COUNTER, "obs/engine_telemetry.py"),
    # Per-request cost attribution (docs/observability.md "Cost
    # attribution"): device-seconds per finished request + the per-tenant
    # chip-time billing meter and its audit denominator.
    MetricSpec("pst_request_device_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_tenant_device_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_device_busy_seconds", COUNTER, "obs/engine_telemetry.py"),
    # The engine's own clock for the device (engine/runner.py
    # ``_ReadyClock``): each launched program's service time from
    # ready-to-ready stamps, the idle between, and the step thread's wall
    # by what the loop was doing (docs/observability.md "The device's
    # time, from the engine").
    MetricSpec("pst_engine_device_step_seconds", HISTOGRAM, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_device_service_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_device_idle_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_loop_seconds", COUNTER, "obs/engine_telemetry.py"),
    MetricSpec("pst_engine_loop_cycles", COUNTER, "obs/engine_telemetry.py"),
    # --- resilience/metrics.py: breakers, deadlines, hedges, resume -----
    MetricSpec("pst_resilience_breaker_state", GAUGE, "resilience/metrics.py"),
    MetricSpec("pst_resilience_breaker_transitions_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_retries_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_failovers_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_upstream_failures_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_admitted_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_sheds_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_queue_depth", GAUGE, "resilience/metrics.py"),
    MetricSpec("pst_resilience_client_disconnects_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_resilience_draining_engines", GAUGE, "resilience/metrics.py"),
    MetricSpec("pst_resilience_warming_engines", GAUGE, "resilience/metrics.py"),
    MetricSpec("pst_deadline_budget_ms", HISTOGRAM, "resilience/metrics.py"),
    MetricSpec("pst_deadline_sheds_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_hedge_fired_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_hedge_won_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_hedge_cancelled_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_hedge_suppressed_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_stream_resume_attempts_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_stream_resume_success_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_stream_resume_failures_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_stream_truncated_total", COUNTER, "resilience/metrics.py"),
    # Multi-tenant QoS (docs/multi-tenancy.md): per-tenant admission,
    # queue depth and usage metering.
    MetricSpec("pst_tenant_admitted_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_tenant_sheds_total", COUNTER, "resilience/metrics.py"),
    MetricSpec("pst_tenant_queue_depth", GAUGE, "resilience/metrics.py"),
    MetricSpec("pst_tenant_usage_tokens_total", COUNTER, "resilience/metrics.py"),
    # --- router/routing/metrics.py: fleet routing ------------------------
    # --- router/services/disagg.py: disaggregated P/D pools -------------
    MetricSpec("pst_disagg_transfer_seconds", HISTOGRAM, "router/services/disagg.py"),
    MetricSpec("pst_disagg_overlap_seconds", HISTOGRAM, "router/services/disagg.py"),
    MetricSpec("pst_disagg_fallback", COUNTER, "router/services/disagg.py"),
    MetricSpec("pst_route_score", HISTOGRAM, "router/routing/metrics.py"),
    MetricSpec("pst_route_spill", COUNTER, "router/routing/metrics.py"),
    MetricSpec("pst_route_session_remap", COUNTER, "router/routing/metrics.py"),
    MetricSpec("pst_route_lookup_skipped", COUNTER, "router/routing/metrics.py"),
    # --- router/state/metrics.py: router HA / replication ----------------
    MetricSpec("pst_router_replica_peers", GAUGE, "router/state/metrics.py"),
    MetricSpec("pst_router_replica_sync", COUNTER, "router/state/metrics.py"),
    MetricSpec("pst_router_replica_sync_seconds", HISTOGRAM, "router/state/metrics.py"),
    MetricSpec("pst_router_replica_admission_share", GAUGE, "router/state/metrics.py"),
    MetricSpec("pst_router_replica_journals", GAUGE, "router/state/metrics.py"),
    MetricSpec("pst_router_replica_takeovers", COUNTER, "router/state/metrics.py"),
    # --- router/services/metrics_service.py: router process + SLO -------
    MetricSpec("pst_router:cpu_percent", GAUGE, "router/services/metrics_service.py"),
    MetricSpec("pst_router:memory_mb", GAUGE, "router/services/metrics_service.py"),
    MetricSpec("pst_router:disk_percent", GAUGE, "router/services/metrics_service.py"),
    MetricSpec("pst_slo_requests", COUNTER, "router/services/metrics_service.py"),
    MetricSpec("pst_slo_ttft_within_target", COUNTER, "router/services/metrics_service.py"),
    MetricSpec("pst_tenant_slo_requests", COUNTER, "router/services/metrics_service.py"),
    MetricSpec("pst_tenant_slo_ttft_within_target", COUNTER, "router/services/metrics_service.py"),
    MetricSpec("pst_canary_ttft_seconds", GAUGE, "router/services/metrics_service.py"),
    MetricSpec("pst_canary_failures", COUNTER, "router/services/metrics_service.py"),
    # --- router/services/fleet.py: fleet introspection plane ------------
    MetricSpec("pst_fleet_engines", GAUGE, "router/services/fleet.py"),
    # --- router/services/capacity.py: autoscaler capacity signals -------
    MetricSpec("pst_capacity_saturation", GAUGE, "router/services/capacity.py"),
    MetricSpec("pst_capacity_burn_rate", GAUGE, "router/services/capacity.py"),
    MetricSpec("pst_capacity_replica_hint", GAUGE, "router/services/capacity.py"),
    MetricSpec("pst_capacity_queue_depth_slope", GAUGE, "router/services/capacity.py"),
    MetricSpec("pst_capacity_kv_headroom", GAUGE, "router/services/capacity.py"),
)

BY_NAME: Dict[str, MetricSpec] = {s.name: s for s in REGISTRY}


def declared_names() -> Tuple[str, ...]:
    return tuple(s.name for s in REGISTRY)


def exposition_names() -> Tuple[str, ...]:
    return tuple(s.exposition_name for s in REGISTRY)
