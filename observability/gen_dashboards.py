#!/usr/bin/env python3
"""Generate the Grafana dashboards + Prometheus rules (run this file).

Reference role: observability/vllm-dashboard.json (20 fleet panels) and the
LMCache dashboard configmap. Panels AND the SLO recording/alerting rules
(prometheus-rules.yaml) are generated so metric names stay in sync with
the code in one place — CI diffs the committed artifacts against this
generator's output.
"""

import json
import os

DS = {"type": "prometheus", "uid": "${datasource}"}

# TTFT SLO objective the burn-rate rules alert on: 99% of generation
# requests see first token within the configured target (--slo-ttft-ms,
# default the 200 ms north star). Error budget = 1 - objective.
SLO_OBJECTIVE = 0.99
SLO_ERROR_BUDGET = round(1.0 - SLO_OBJECTIVE, 6)


def panel(title, exprs, x, y, w=8, h=7, unit="short", kind="timeseries"):
    targets = [
        {"expr": expr, "legendFormat": legend, "refId": chr(65 + i),
         "datasource": DS}
        for i, (expr, legend) in enumerate(exprs)
    ]
    return {
        "title": title,
        "type": kind,
        "datasource": DS,
        "gridPos": {"x": x, "y": y, "w": w, "h": h},
        "fieldConfig": {"defaults": {"unit": unit}, "overrides": []},
        "targets": targets,
        "options": {"legend": {"displayMode": "list", "placement": "bottom"}},
    }


def stat(title, expr, x, y, w=4, h=4, unit="short"):
    p = panel(title, [(expr, "")], x, y, w, h, unit, kind="stat")
    p["options"] = {"reduceOptions": {"calcs": ["lastNotNull"]}}
    return p


def dashboard(uid, title, panels):
    return {
        "uid": uid,
        "title": title,
        "tags": ["production-stack-tpu"],
        "timezone": "browser",
        "schemaVersion": 39,
        "version": 1,
        "refresh": "15s",
        "time": {"from": "now-30m", "to": "now"},
        "templating": {
            "list": [
                {
                    "name": "datasource",
                    "type": "datasource",
                    "query": "prometheus",
                    "current": {},
                }
            ]
        },
        "panels": panels,
    }


def fleet_dashboard():
    """Reference vllm-dashboard.json parity: fleet + router health."""
    p = []
    # Row 1 — fleet stats.
    p.append(stat("Available Engines",
                  'count(vllm:num_requests_running)', 0, 0))
    p.append(stat("Running Requests",
                  'sum(vllm:num_requests_running)', 4, 0))
    p.append(stat("Pending Requests",
                  'sum(vllm:num_requests_waiting)', 8, 0))
    p.append(stat("KV Hit Rate",
                  'avg(vllm:gpu_prefix_cache_hit_rate)', 12, 0,
                  unit="percentunit"))
    p.append(stat("KV Usage",
                  'max(vllm:gpu_cache_usage_perc)', 16, 0,
                  unit="percentunit"))
    p.append(stat("Preempted (swapped)",
                  'sum(vllm:num_requests_swapped)', 20, 0))
    # Row 2 — latency distributions.
    p.append(panel("Request TTFT distribution (p50/p90/p99)", [
        ('histogram_quantile(0.5, sum(rate(vllm:time_to_first_token_seconds_bucket[2m])) by (le))', "p50"),
        ('histogram_quantile(0.9, sum(rate(vllm:time_to_first_token_seconds_bucket[2m])) by (le))', "p90"),
        ('histogram_quantile(0.99, sum(rate(vllm:time_to_first_token_seconds_bucket[2m])) by (le))', "p99"),
    ], 0, 4, unit="s"))
    p.append(panel("Request latency distribution (p50/p90/p99)", [
        ('histogram_quantile(0.5, sum(rate(vllm:e2e_request_latency_seconds_bucket[2m])) by (le))', "p50"),
        ('histogram_quantile(0.9, sum(rate(vllm:e2e_request_latency_seconds_bucket[2m])) by (le))', "p90"),
        ('histogram_quantile(0.99, sum(rate(vllm:e2e_request_latency_seconds_bucket[2m])) by (le))', "p99"),
    ], 8, 4, unit="s"))
    p.append(panel("QPS (successful requests/s)", [
        ('sum(rate(vllm:request_success_total[2m]))', "qps"),
    ], 16, 4))
    # Row 3 — throughput + per-engine load.
    p.append(panel("Token throughput", [
        ('sum(rate(vllm:generation_tokens_total[2m]))', "generation tok/s"),
        ('sum(rate(vllm:prompt_tokens_total[2m]))', "prompt tok/s"),
    ], 0, 11))
    p.append(panel("Running requests per engine", [
        ('vllm:num_requests_running', "{{model_name}}"),
    ], 8, 11))
    p.append(panel("KV cache usage per engine", [
        ('vllm:gpu_cache_usage_perc', "{{model_name}}"),
    ], 16, 11, unit="percentunit"))
    # Row 4 — prefix cache + router process.
    p.append(panel("Prefix cache hit rate per engine", [
        ('vllm:gpu_prefix_cache_hit_rate', "{{model_name}}"),
    ], 0, 18, unit="percentunit"))
    p.append(panel("Router process", [
        ('pst_router:cpu_percent', "cpu %"),
        ('pst_router:memory_mb', "memory MB"),
        ('pst_router:disk_percent', "disk %"),
    ], 8, 18))
    p.append(panel("Router request stats (QPS per backend)", [
        ('vllm:current_qps', "{{server}}"),
    ], 16, 18))
    # Row 5 — speculative decoding (engines started with --speculative-ngram
    # or, for a model that drafts its own next token, --speculative-mtp).
    p.append(panel("Speculative decode: draft vs accepted tok/s", [
        ('sum(rate(vllm:spec_decode_num_draft_tokens_total[2m]))', "drafted"),
        ('sum(rate(vllm:spec_decode_num_accepted_tokens_total[2m]))',
         "accepted"),
    ], 0, 25))
    p.append(panel("Speculative decode: acceptance rate", [
        ('sum(rate(vllm:spec_decode_num_accepted_tokens_total[2m])) / '
         'clamp_min(sum(rate(vllm:spec_decode_num_draft_tokens_total[2m])),'
         ' 1e-9)', "accept rate"),
    ], 8, 25, unit="percentunit"))
    # A verify-and-draft step yields one token a live row and one more where
    # the device accepted its draft: 1.0 the floor, 2.0 the ceiling. Read it
    # beside the decode step histogram below (the step costs two positions).
    p.append(panel("Tokens a row and verify-and-draft step (--speculative-mtp)", [
        ('sum(rate(pst:mtp_tokens_emitted_total[2m])) / '
         'clamp_min(sum(rate(pst:mtp_row_steps_total[2m])), 1e-9)',
         "tokens / row-step"),
        ('sum(rate(pst:mtp_steps_total[2m]))', "steps /s"),
    ], 16, 25))
    # Row 6 — fleet hit rate (the ≥0.6 north star) + live-KV swap.
    p.append(panel("Fleet KV hit rate (all engines)", [
        ('sum(vllm:gpu_prefix_cache_hits_total) / '
         'clamp_min(sum(vllm:gpu_prefix_cache_queries_total), 1)', "fleet"),
        ('0.6', "north star (0.6)"),
    ], 0, 32, unit="percentunit"))
    p.append(panel("KV swap traffic (park / resume / tail pages)", [
        ('sum(rate(pst:kv_swap_out_total[2m]))', "swap-out /s"),
        ('sum(rate(pst:kv_swap_in_total[2m]))', "swap-in /s"),
        ('sum(rate(pst:kv_swap_tail_pages_total[2m]))', "tail pages /s"),
        ('sum(rate(pst:kv_swap_fallback_recompute_total[2m]))',
         "fallback recompute /s"),
    ], 8, 32))
    p.append(panel("KV swap stash occupancy (host DRAM pages)", [
        ('sum(pst:kv_swap_stash_blocks)', "stashed pages"),
        ('sum(vllm:num_requests_swapped)', "parked sequences"),
    ], 16, 32))
    # Row 7 — resilience (breakers, retry/failover, admission, drain).
    p.append(panel("Circuit breaker state per engine (0=closed, 1=half-open, 2=open)", [
        ('pst_resilience_breaker_state', "{{server}}"),
    ], 0, 39))
    p.append(panel("Retries / failovers / upstream failures per second", [
        ('sum(rate(pst_resilience_retries_total[2m]))', "retries /s"),
        ('sum(rate(pst_resilience_failovers_total[2m]))', "failovers /s"),
        ('sum(rate(pst_resilience_upstream_failures_total[2m]))',
         "upstream failures /s"),
        ('sum(rate(pst_resilience_client_disconnects_total[2m]))',
         "client disconnects /s"),
    ], 8, 39))
    p.append(panel("Admission control (admitted vs shed, queue depth)", [
        ('sum(rate(pst_resilience_admitted_total[2m]))', "admitted /s"),
        ('sum(rate(pst_resilience_sheds_total[2m])) by (reason)',
         "shed {{reason}} /s"),
        ('pst_resilience_queue_depth', "queue depth"),
    ], 16, 39))
    p.append(stat("Open breakers",
                  'count(pst_resilience_breaker_state == 2) or vector(0)',
                  0, 46))
    p.append(stat("Draining engines",
                  'pst_resilience_draining_engines', 4, 46))
    # Row 8 — deadlines & hedging (docs/resilience.md).
    p.append(panel("Request budget at admission (p50/p90/p99 ms)", [
        ('histogram_quantile(0.5, sum(rate(pst_deadline_budget_ms_bucket[2m])) by (le))', "p50"),
        ('histogram_quantile(0.9, sum(rate(pst_deadline_budget_ms_bucket[2m])) by (le))', "p90"),
        ('histogram_quantile(0.99, sum(rate(pst_deadline_budget_ms_bucket[2m])) by (le))', "p99"),
    ], 0, 50, unit="ms"))
    p.append(panel("Deadline sheds by stage (router + engine)", [
        ('sum(rate(pst_deadline_sheds_total[2m])) by (stage)',
         "router {{stage}} /s"),
        ('sum(rate(pst:deadline_shed_admission[2m]))', "engine admission /s"),
        ('sum(rate(pst:deadline_shed_queued[2m]))', "engine queued /s"),
        ('sum(rate(pst:deadline_shed_running[2m]))', "engine running /s"),
    ], 8, 50))
    p.append(panel("Hedging (fired / won / cancelled / suppressed)", [
        ('sum(rate(pst_hedge_fired_total[2m]))', "fired /s"),
        ('sum(rate(pst_hedge_won_total[2m]))', "won /s"),
        ('sum(rate(pst_hedge_cancelled_total[2m]))', "cancelled /s"),
        ('sum(rate(pst_hedge_suppressed_total[2m])) by (reason)',
         "suppressed {{reason}} /s"),
    ], 16, 50))
    p.append(stat("Hedge win rate (2m)",
                  'sum(rate(pst_hedge_won_total[2m])) / '
                  'clamp_min(sum(rate(pst_hedge_fired_total[2m])), 1e-9)',
                  0, 57))
    p.append(stat("Deadline sheds /s",
                  'sum(rate(pst_deadline_sheds_total[2m])) + '
                  'sum(rate(pst:deadline_shed_queued[2m])) + '
                  'sum(rate(pst:deadline_shed_running[2m])) or vector(0)',
                  4, 57))
    # Stream resumption (docs/resilience.md "Stream resumption"): broken
    # streams continued on another engine vs visibly truncated.
    p.append(panel("Stream resume / truncation", [
        ('sum(rate(pst_stream_resume_attempts_total[2m]))',
         "resume legs /s"),
        ('sum(rate(pst_stream_resume_success_total[2m]))', "resumed /s"),
        ('sum(rate(pst_stream_resume_failures_total[2m]))',
         "resume failed /s"),
        ('sum(rate(pst_stream_truncated_total[2m])) by (reason)',
         "truncated {{reason}} /s"),
    ], 8, 57))
    p.append(stat("Truncated streams /s",
                  'sum(rate(pst_stream_truncated_total[2m])) or vector(0)',
                  16, 57))
    # Row 9 — latency breakdown (pst_stage_duration_seconds, from the
    # request-tracing span recorder): the true TTFT decomposition — router
    # admission / routing / proxy vs engine queue / prefill / decode /
    # KV-tier fetches — replacing guesswork over whole-request averages.
    p.append(panel("Latency breakdown: router stages p90", [
        ('histogram_quantile(0.9, sum(rate(pst_stage_duration_seconds_bucket'
         '{component="router"}[2m])) by (le, stage))', "{{stage}}"),
    ], 0, 61, unit="s"))
    p.append(panel("Latency breakdown: engine stages p90", [
        ('histogram_quantile(0.9, sum(rate(pst_stage_duration_seconds_bucket'
         '{component="engine"}[2m])) by (le, stage))', "{{stage}}"),
    ], 8, 61, unit="s"))
    p.append(panel("Mean stage time per request (all components)", [
        ('sum(rate(pst_stage_duration_seconds_sum[2m])) by (stage) / '
         'clamp_min(sum(rate(pst_stage_duration_seconds_count[2m])) '
         'by (stage), 1e-9)', "{{stage}}"),
    ], 16, 61, unit="s"))
    # Row 10 — TPU engine telemetry (docs/observability.md "Engine
    # telemetry"): compiles, step durations, throughput, KV pressure,
    # padding waste, startup decomposition.
    p.append(panel("XLA compiles per second (by step kind)", [
        ('sum(rate(pst_engine_compile_total[5m])) by (kind)', "{{kind}}"),
    ], 0, 68))
    p.append(panel("Compile time p90 (first call per shape bucket)", [
        ('histogram_quantile(0.9, sum(rate(pst_engine_compile_seconds_bucket'
         '[10m])) by (le, kind))', "{{kind}}"),
    ], 8, 68, unit="s"))
    # The device's own time of a launched program, by the engine's
    # completion clock (pst_engine_step_duration_seconds is the host's wall
    # around the dispatch call: under chained decode not the device's time).
    p.append(panel("Device step time p50 / p90 by kind (engine's clock)", [
        ('histogram_quantile(0.5, sum(rate('
         'pst_engine_device_step_seconds_bucket[2m])) by (le, kind))',
         "{{kind}} p50"),
        ('histogram_quantile(0.9, sum(rate('
         'pst_engine_device_step_seconds_bucket[2m])) by (le, kind))',
         "{{kind}} p90"),
    ], 16, 68, unit="s"))
    p.append(panel("Engine tokens/s (by step kind)", [
        ('sum(pst_engine_tokens_per_second) by (kind)', "{{kind}} tok/s"),
    ], 0, 75))
    p.append(panel("Batch fill ratio (padding waste; 1.0 = none)", [
        ('sum(rate(pst_engine_batch_fill_ratio_sum[2m])) by (kind) / '
         'clamp_min(sum(rate(pst_engine_batch_fill_ratio_count[2m])) '
         'by (kind), 1e-9)', "{{kind}}"),
    ], 8, 75, unit="percentunit"))
    p.append(panel("KV page occupancy vs high watermark", [
        ('pst_engine_kv_page_occupancy', "occupancy"),
        ('pst_engine_kv_page_high_watermark', "high watermark"),
    ], 16, 75, unit="percentunit"))
    p.append(panel("Engine startup decomposition (s)", [
        ('pst_engine_startup_seconds', "{{phase}}"),
    ], 0, 82, unit="s"))
    p.append(panel("Preemptions / swaps per second (engine view)", [
        ('sum(rate(pst_engine_preemptions_total[2m]))', "preemptions /s"),
        ('sum(rate(pst_engine_swap_out_total[2m]))', "swap-out /s"),
        ('sum(rate(pst_engine_swap_in_total[2m]))', "swap-in /s"),
    ], 8, 82))
    p.append(stat("Compiles (1h)",
                  'sum(increase(pst_engine_compile_total[1h])) or vector(0)',
                  16, 82))
    # Row 11 — SLO (docs/observability.md "SLOs & alerting"): attainment
    # ratios, multi-window burn rates, canary probes. The recorded series
    # come from observability/prometheus-rules.yaml (same generator).
    p.append(panel("TTFT SLO attainment (good / total)", [
        ('1 - pst:slo_ttft_error:ratio_rate5m', "5m"),
        ('1 - pst:slo_ttft_error:ratio_rate1h', "1h"),
        ('1 - pst:slo_ttft_error:ratio_rate3d', "3d"),
        (str(SLO_OBJECTIVE), f"objective ({SLO_OBJECTIVE})"),
    ], 0, 89, unit="percentunit"))
    p.append(panel("SLO burn rate (error ratio / budget)", [
        (f'pst:slo_ttft_error:ratio_rate1h / {SLO_ERROR_BUDGET}', "1h"),
        (f'pst:slo_ttft_error:ratio_rate6h / {SLO_ERROR_BUDGET}', "6h"),
        (f'pst:slo_ttft_error:ratio_rate3d / {SLO_ERROR_BUDGET}', "3d"),
        ('14.4', "page threshold (14.4x)"),
        ('1', "ticket threshold (1x)"),
    ], 8, 89))
    p.append(panel("Canary TTFT per engine", [
        ('pst_canary_ttft_seconds', "{{engine}}"),
    ], 16, 89, unit="s"))
    p.append(stat("SLO requests /s",
                  'sum(rate(pst_slo_requests_total[5m])) or vector(0)',
                  0, 96))
    p.append(stat("Canary failures /10m",
                  'sum(increase(pst_canary_failures_total[10m])) or vector(0)',
                  4, 96))
    # Row 12 — Router HA / replication (docs/router-ha.md): membership,
    # sync health, fleet admission shares, journal takeovers. Flat at
    # single replica; the interesting traces appear the moment
    # routerSpec.replicaCount > 1.
    p.append(panel("Router replicas: membership + admission share", [
        ('min(pst_router_replica_peers)', "live replicas (min view)"),
        ('sum(pst_router_replica_admission_share)',
         "sum of admission shares (should be ~1)"),
    ], 0, 100))
    p.append(panel("State-sync exchanges by outcome", [
        ('sum(rate(pst_router_replica_sync_total[2m])) by (outcome)',
         "{{outcome}} /s"),
        ('histogram_quantile(0.9, sum(rate('
         'pst_router_replica_sync_seconds_bucket[5m])) by (le))',
         "exchange p90 (s)"),
    ], 8, 100))
    p.append(panel("Journal checkpoints + takeovers", [
        ('sum(pst_router_replica_journals) by (kind)', "{{kind}} journals"),
        ('sum(rate(pst_router_replica_takeovers_total[5m])) by (outcome)',
         "takeover {{outcome}} /s"),
    ], 16, 100))
    # Row 13 — Fleet routing (docs/router.md "Fleet routing"): the fused
    # scoring policy's health. Score quantiles collapse when the fleet
    # loses warm prefixes (churn) or KV headroom; spills/remaps show the
    # bounded-load and session-eviction machinery actually working.
    p.append(panel("Fleet routing: chosen-engine score (p50/p90)", [
        ('histogram_quantile(0.5, sum(rate(pst_route_score_bucket[5m])) by (le))',
         "score p50"),
        ('histogram_quantile(0.9, sum(rate(pst_route_score_bucket[5m])) by (le))',
         "score p90"),
    ], 0, 107))
    p.append(panel("Fleet routing: spills + session remaps", [
        ('sum(rate(pst_route_spill_total[5m])) by (reason)',
         "spill {{reason}} /s"),
        ('sum(rate(pst_route_session_remap_total[5m])) by (reason)',
         "remap {{reason}} /s"),
    ], 8, 107))
    p.append(panel("Fleet routing: kvserver lookups skipped", [
        ('sum(rate(pst_route_lookup_skipped_total[5m])) by (reason)',
         "skipped {{reason}} /s"),
    ], 16, 107))

    # Row 14 — Fleet observability plane (docs/observability.md "Fleet
    # debugging" / "Structured logging"): engine phase census (the scalar
    # twin of GET /debug/fleet), structured-log sampler drops, and the
    # exemplar-linked stage p99 — with OpenMetrics negotiated, the stage
    # buckets carry trace_id exemplars, so this panel's dots link
    # straight to /debug/requests timelines.
    p.append(panel("Fleet: engines by phase (/debug/fleet census)", [
        ('pst_fleet_engines', "{{state}}"),
    ], 0, 114))
    p.append(panel("Structured-log sampler drops", [
        ('sum(rate(pst_log_dropped_total[5m])) by (component)',
         "{{component}} dropped/s"),
    ], 8, 114))
    stage_p99 = panel("Stage p99 (exemplar-linked to /debug/requests)", [
        ('histogram_quantile(0.99, sum(rate('
         'pst_stage_duration_seconds_bucket[5m])) by (le, component))',
         "{{component}} p99"),
    ], 16, 114, unit="s")
    # Grafana renders exemplar dots on this panel when the Prometheus
    # datasource has exemplar storage enabled.
    for t in stage_p99["targets"]:
        t["exemplar"] = True
    p.append(stage_p99)

    # Row 15 — Capacity & cost (docs/observability.md "Capacity signals"
    # / "Cost attribution"): the in-process autoscaler input
    # (GET /autoscale/signal's gauge twins) and the chip-time billing
    # meter. replica_hint vs ready engines is the "do we need more
    # chips?" panel; tenant device-seconds is the bill.
    p.append(panel("Capacity: saturation + replica hint", [
        ('pst_capacity_saturation', "saturation"),
        ('pst_capacity_replica_hint', "replica hint"),
        ('pst_fleet_engines{state="ready"}', "ready engines"),
    ], 0, 121))
    p.append(panel("Capacity: in-process burn rate + queue slope", [
        ('pst_capacity_burn_rate{window="5m"}', "burn 5m"),
        ('pst_capacity_burn_rate{window="1h"}', "burn 1h"),
        ('pst_capacity_queue_depth_slope', "queue slope /s"),
        ('pst_capacity_kv_headroom', "kv headroom"),
    ], 8, 121))
    p.append(panel("Cost: tenant chip-seconds + the device's busy, idle "
                   "and seen-late share", [
        ('sum(rate(pst_tenant_device_seconds_total[5m])) by (tenant)',
         "{{tenant}} chip-s/s"),
        ('histogram_quantile(0.9, sum(rate('
         'pst_request_device_seconds_bucket[5m])) by (le, phase))',
         "{{phase}} p90 device-s"),
        ('sum(rate(pst_engine_device_busy_seconds_total[5m]))',
         "device busy s/s"),
        ('sum(rate(pst_engine_device_idle_seconds_total[5m])) by (state)',
         "device idle s/s: {{state}}"),
        ('sum(rate(pst_engine_device_service_seconds_total{seen="late"}[5m]))'
         ' / clamp_min(sum(rate(pst_engine_device_busy_seconds_total[5m])), '
         '1e-9)', "seen-late share of busy"),
    ], 16, 121))
    p.append(stat("Attribution coverage (5m)",
                  'clamp_max(sum(rate(pst_request_device_seconds_sum[5m])) / '
                  'clamp_min(sum(rate('
                  'pst_engine_device_busy_seconds_total[5m])), 1e-9), 2)',
                  0, 128, unit="percentunit"))
    p.append(panel("Flight recorder: persisted snapshots", [
        ('sum(increase(pst_engine_flight_snapshots_persisted_total[1h]))',
         "snapshots persisted/h"),
    ], 4, 128))
    # What held the step loop off (docs/observability.md "Flight
    # recorder"): seconds past the bar by cause, and the collections'
    # share of the wall beside them.
    p.append(panel("Stalls by cause", [
        ('sum(rate(pst_engine_stall_seconds_total[5m])) by (cause)',
         "{{cause}} s stalled /s"),
        ('sum(rate(pst_engine_gc_pause_seconds_total[5m]))',
         "collections s paused /s"),
        # the window account: the step thread's wall by what the loop was
        # doing (the states sum to 1 s/s an engine)
        ('sum(rate(pst_engine_loop_seconds_total[5m])) by (state)',
         "loop s/s: {{state}}"),
    ], 12, 128))

    # Page groups of a model whose window layers keep their own
    # (docs/engine.md "Model classes"): residency by group, pages released
    # below the window, and the share of prefill positions that ran the
    # cross-decoder.
    p.append(panel("KV pages in use by group", [
        ('sum({__name__="pst:kv_pages_in_use"}) by (group)', "{{group}}"),
        ('sum({__name__="pst:state_slots_in_use"})', "state slots"),
        ('sum(rate({__name__="pst:window_pages_released_total"}[5m]))',
         "window pages released/s"),
    ], 0, 129))
    p.append(panel("Window residency and skipped cross-decoder", [
        ('sum(rate({__name__="pst:window_page_steps_total"}[5m])) / '
         'sum(rate({__name__="pst:window_whole_context_page_steps_total"}[5m]))',
         "window pages held / whole context"),
        ('sum(rate({__name__="pst:cross_decoder_positions_total"}[5m])) / '
         'sum(rate({__name__="pst:prefill_tokens_total"}[5m]))',
         "cross-decoder positions / prefill tokens"),
        ('sum(rate({__name__="pst:prefill_tokens_total"}[5m])) / '
         'sum(rate({__name__="pst:prefill_bucket_positions_total"}[5m]))',
         "prefill tokens / bucket positions"),
    ], 8, 129, unit="percentunit"))

    # Row 16 — Disagg (docs/disagg.md): the streamed P/D handoff's
    # health. Overlap p50 vs transfer p50 shows how much of the prefill
    # wall the decode leg hides; fallbacks by reason is the degradation
    # ledger (every one of them served fused with no client error).
    p.append(panel("Disagg: transfer vs overlap (p50)", [
        ('histogram_quantile(0.5, sum(rate('
         'pst_disagg_transfer_seconds_bucket[5m])) by (le))',
         "transfer p50"),
        ('histogram_quantile(0.5, sum(rate('
         'pst_disagg_overlap_seconds_bucket[5m])) by (le))',
         "overlap p50"),
    ], 0, 132, unit="s"))
    p.append(panel("Disagg: fused-path fallbacks", [
        ('sum(rate(pst_disagg_fallback_total[5m])) by (reason)',
         "{{reason}} /s"),
    ], 8, 132))
    p.append(panel("Disagg: KV pages published vs prefetched", [
        ('sum(rate({__name__="pst:kv_published_blocks_total"}[5m]))',
         "published/s"),
        ('sum(rate({__name__="pst:kv_prefetched_blocks_total"}[5m]))',
         "prefetched/s"),
        ('sum(rate({__name__="pst:kv_transfer_fallbacks_total"}[5m]))',
         "engine fallbacks/s"),
    ], 16, 132))
    # Row 19 — the engine's step loop by phase (docs/observability.md
    # "Profiling"): where the host's share of a step goes, on the same
    # names a profile capture shows as pst.* spans.
    p.append(panel("Step loop: mean wall per step, by phase", [
        ('sum(rate(pst_engine_step_phase_seconds_sum[2m])) by (phase, kind) '
         '/ clamp_min(sum(rate(pst_engine_step_phase_seconds_count[2m])) '
         'by (phase, kind), 1e-9)', "{{phase}} {{kind}}"),
    ], 0, 139, w=16, unit="s"))
    return dashboard("pst-fleet", "production-stack-tpu / Fleet", p)


def tiering_dashboard():
    """LMCache-dashboard parity: offload tier behavior."""
    p = []
    p.append(stat("Host-tier hit blocks",
                  'sum(vllm:kv_offload_host_hit_blocks)', 0, 0))
    p.append(stat("Remote-tier hit blocks",
                  'sum(vllm:kv_offload_remote_hit_blocks)', 4, 0))
    p.append(stat("Spilled blocks",
                  'sum(vllm:kv_offload_spilled_blocks)', 8, 0))
    p.append(panel("TTFT (warm vs target)", [
        ('histogram_quantile(0.5, sum(rate(vllm:time_to_first_token_seconds_bucket[2m])) by (le))', "p50"),
    ], 0, 4, unit="s"))
    p.append(panel("Offload activity", [
        ('rate(vllm:kv_offload_spilled_blocks[2m])', "spills/s"),
        ('rate(vllm:kv_offload_host_hit_blocks[2m])', "host hits/s"),
        ('rate(vllm:kv_offload_remote_hit_blocks[2m])', "remote hits/s"),
    ], 8, 4))
    p.append(panel("Prefix cache hits vs queries", [
        ('sum(rate(vllm:gpu_prefix_cache_hits_total[2m]))', "hit tokens/s"),
        ('sum(rate(vllm:gpu_prefix_cache_queries_total[2m]))', "query tokens/s"),
    ], 16, 4))
    return dashboard("pst-kv-tiering", "production-stack-tpu / KV Tiering", p)


def _slo_error_expr(window):
    # (requests - within) / requests, NOT 1 - within/requests: with zero
    # traffic both rates are 0 and this form reads 0/1e-9 = 0 error — an
    # idle fleet must never page (the 1-minus form reads error = 1 there).
    return (
        f"(sum(rate(pst_slo_requests_total[{window}])) - "
        f"sum(rate(pst_slo_ttft_within_target_total[{window}]))) / "
        f"clamp_min(sum(rate(pst_slo_requests_total[{window}])), 1e-9)"
    )


def prometheus_rules():
    """Recording rules + multi-window multi-burn-rate alerts for the TTFT
    SLO (the standard SRE-workbook shape: page when the 1h AND 5m burn
    rates both exceed 14.4x the error budget — budget gone in ~2 days;
    ticket when the 3d AND 6h burn rates exceed 1x — budget gone in 30d),
    plus engine-health alerts over the pst_engine_* telemetry."""
    windows = ["5m", "30m", "1h", "6h", "3d"]
    recording = [
        {
            "record": f"pst:slo_ttft_error:ratio_rate{w}",
            "expr": _slo_error_expr(w),
        }
        for w in windows
    ]
    page_thresh = round(14.4 * SLO_ERROR_BUDGET, 6)
    ticket_thresh = round(1.0 * SLO_ERROR_BUDGET, 6)
    alerts = [
        {
            "alert": "PstTtftSloBurnRatePage",
            "expr": (
                f"pst:slo_ttft_error:ratio_rate1h > {page_thresh} "
                f"and pst:slo_ttft_error:ratio_rate5m > {page_thresh}"
            ),
            "for": "2m",
            "labels": {"severity": "page", "slo": "ttft"},
            "annotations": {
                "summary": "TTFT SLO burning at >=14.4x (budget gone in ~2 days)",
                "description": (
                    "The fleet is missing the TTFT target fast enough to "
                    "exhaust the 30-day error budget within ~2 days "
                    f"(objective {SLO_OBJECTIVE}, 1h AND 5m windows). "
                    "Check the Latency breakdown and TPU engine dashboard "
                    "rows: recompiles (pst_engine_compile_total) and KV "
                    "pressure (pst_engine_kv_page_occupancy) are the usual "
                    "suspects."
                ),
            },
        },
        {
            "alert": "PstTtftSloBurnRateTicket",
            "expr": (
                f"pst:slo_ttft_error:ratio_rate3d > {ticket_thresh} "
                f"and pst:slo_ttft_error:ratio_rate6h > {ticket_thresh}"
            ),
            "for": "1h",
            "labels": {"severity": "ticket", "slo": "ttft"},
            "annotations": {
                "summary": "TTFT SLO burning at >=1x (budget gone in 30 days)",
                "description": (
                    "Slow, sustained burn: at this rate the 30-day TTFT "
                    "error budget will be fully spent (3d AND 6h windows). "
                    "File and investigate; no page."
                ),
            },
        },
        {
            "alert": "PstEngineRecompileOnLiveTraffic",
            # Per-instance, uptime-gated: cold-start compiles during the
            # first 15 minutes of an engine's life are the expected warmup
            # set — a rolling deploy must not raise standing tickets.
            "expr": (
                "sum by (instance) "
                "(increase(pst_engine_compile_total[15m])) > 0 "
                "and on (instance) sum by (instance) "
                "(vllm:num_requests_running) > 0 "
                "and on (instance) "
                "((time() - pst_engine_start_time_seconds) > 900)"
            ),
            "for": "0m",
            "labels": {"severity": "ticket", "component": "engine"},
            "annotations": {
                "summary": "XLA recompile landed while requests were live",
                "description": (
                    "A compiled-shape-bucket miss hit a serving engine. "
                    "The victim request's timeline carries a `compile` span event; "
                    "widen --min-decode-bucket or pre-warm the offending "
                    "bucket (kind/shape_bucket labels name it)."
                ),
            },
        },
        {
            "alert": "PstCanaryTtftHigh",
            "expr": "pst_canary_ttft_seconds > 1",
            "for": "5m",
            "labels": {"severity": "ticket", "component": "router"},
            "annotations": {
                "summary": "Canary TTFT above 1s on {{ $labels.engine }}",
                "description": (
                    "The synthetic 1-token probe is slow on this engine "
                    "even without user load — cold path, pending compile, "
                    "or host contention."
                ),
            },
        },
        {
            "alert": "PstCanaryFailing",
            "expr": "sum(increase(pst_canary_failures_total[10m])) by (engine) > 3",
            "for": "0m",
            "labels": {"severity": "page", "component": "router"},
            "annotations": {
                "summary": "Canary probes failing on {{ $labels.engine }}",
                "description": (
                    "More than 3 failed probes in 10 minutes: the engine "
                    "is unreachable or erroring. The router's breaker "
                    "should already be open; verify capacity."
                ),
            },
        },
    ]
    return {
        "groups": [
            {"name": "pst-slo-recording", "interval": "30s",
             "rules": recording},
            {"name": "pst-slo-alerts", "rules": alerts},
        ]
    }


def _dump_rules_yaml(rules: dict) -> str:
    """Hand-rolled YAML so the generator stays dependency-free (PyYAML is
    a router dependency, not necessarily a tooling one) and the output is
    byte-stable for the CI drift check."""
    def q(s):
        return '"' + str(s).replace("\\", "\\\\").replace('"', '\\"') + '"'

    lines = [
        "# Generated by observability/gen_dashboards.py — do not edit by",
        "# hand (CI diffs this file against the generator output).",
        "groups:",
    ]
    for group in rules["groups"]:
        lines.append(f"  - name: {group['name']}")
        if "interval" in group:
            lines.append(f"    interval: {group['interval']}")
        lines.append("    rules:")
        for rule in group["rules"]:
            head = "record" if "record" in rule else "alert"
            lines.append(f"      - {head}: {rule[head]}")
            lines.append(f"        expr: {q(rule['expr'])}")
            if "for" in rule:
                lines.append(f"        for: {rule['for']}")
            for section in ("labels", "annotations"):
                if section in rule:
                    lines.append(f"        {section}:")
                    for k, v in rule[section].items():
                        lines.append(f"          {k}: {q(v)}")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    for name, dash in [
        ("pst-dashboard.json", fleet_dashboard()),
        ("kv-tiering-dashboard.json", tiering_dashboard()),
    ]:
        with open(os.path.join(here, name), "w") as f:
            json.dump(dash, f, indent=2)
        print("wrote", name)
    with open(os.path.join(here, "prometheus-rules.yaml"), "w") as f:
        f.write(_dump_rules_yaml(prometheus_rules()))
    print("wrote prometheus-rules.yaml")
