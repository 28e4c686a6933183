"""OpenAI-compatible HTTP server for the TPU engine (`pst-engine`).

This is the pod the stack deploys where the reference deploys the
`vllm/vllm-openai` image (`helm/templates/deployment-vllm-multi.yaml:101-118`).
Surface contract (everything the router, stats scraper, operator, and
dashboards depend on — SURVEY.md §1 "Serving engine" row):

- `/v1/models`, `/v1/chat/completions`, `/v1/completions` (SSE streaming),
  `/v1/embeddings`, `/tokenize`, `/detokenize`, `/rerank`, `/v1/rerank`,
  `/score`, `/v1/score`
- `/metrics` with `vllm:`-prefixed gauge names the router's
  `EngineStats.from_vllm_scrape` parses (reference `stats/engine_stats.py:63-76`)
- `/health`, `/is_sleeping`, `/sleep`, `/wake_up` (tutorial 19 drain flow)
- `/v1/load_lora_adapter`, `/v1/unload_lora_adapter` (operator LoRA flow,
  `loraadapter_controller.go:582-611`)
- `/version`

Auth: optional `--api-key` (Bearer) mirroring the chart's vllmApiKey secret.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import threading
import time
from typing import List, Optional

import numpy as np
from aiohttp import web
from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
)

from .. import __version__
from ..logging_utils import init_logger
from ..obs import (
    ENGINE_TELEMETRY,
    ENGINE_TELEMETRY_REGISTRY,
    OBS_REGISTRY,
    SpanRecorder,
    bind_log_context,
    configure_logging,
    debug_requests_response,
    render_registries,
    unbind_log_context,
)
from ..obs.metrics import observe_stage
from ..obs.tasks import spawn_owned
from ..resilience.deadline import DEADLINE_EXCEEDED_HEADER, parse_deadline
from ..protocols import (
    ChatCompletionRequest,
    ChatMessage,
    CompletionRequest,
    EmbeddingRequest,
    ErrorResponse,
    random_id,
)
from .async_engine import AsyncLLMEngine
from .config import EngineConfig
from .sequence import SamplingParams

logger = init_logger(__name__)


def _error(message: str, status: int = 400, etype: str = "invalid_request_error",
           headers: Optional[dict] = None):
    return web.json_response(
        ErrorResponse(message=message, type=etype, code=status).model_dump(),
        status=status,
        headers=headers,
    )


def _drain_error():
    # The X-PST-Draining marker lets the router tell a deliberate drain
    # rejection apart from a backend failure: it reconciles its drain state
    # from live traffic (even with health probes off) instead of tripping
    # the circuit breaker.
    return _error("engine is draining", 503, "service_unavailable",
                  headers={"X-PST-Draining": "1"})


def _warming_error():
    # Same contract as the drain marker, for the startup precompile pass:
    # accepting the request would queue it behind the 46-138 s XLA lattice
    # compile (exactly the cold-engine TTFT warmup exists to prevent), so
    # reject with a marker the router reconciles from live traffic — it
    # marks the endpoint warming and fails over without a breaker penalty.
    return _error("engine is warming up (precompiling)", 503,
                  "service_unavailable", headers={"X-PST-Warming": "1"})


def _deadline_error():
    # Instant 504 for work whose router-propagated budget is already gone:
    # cheaper to shed at HTTP admission than to let the scheduler drop it.
    # The marker header tells the router this was a deliberate budget shed,
    # not an engine failure.
    return _error("deadline exceeded", 504, "deadline_exceeded",
                  headers={DEADLINE_EXCEEDED_HEADER: "1"})


class EngineMetrics:
    """Prometheus surface, `vllm:`-named for scraper/dashboard compatibility."""

    def __init__(self, model: str):
        self.registry = CollectorRegistry()
        label = {"model_name": model}

        def gauge(name, doc, by=()):
            """The model's child; with further labels ``by``, a function
            from their values to the child (as ``counter`` below)."""
            g = Gauge(name, doc, ["model_name", *by], registry=self.registry)
            if by:
                return lambda *values: g.labels(*label.values(), *values)
            return g.labels(**label)

        def counter(name, doc, by=()):
            """The model's child; with further labels ``by``, a function
            from their values to the child."""
            c = Counter(
                name, doc, ["model_name", *by], registry=self.registry
            )
            if by:
                return lambda *values: c.labels(*label.values(), *values)
            return c.labels(**label)

        def hist(name, doc, buckets):
            h = Histogram(
                name, doc, ["model_name"], registry=self.registry, buckets=buckets
            )
            return h.labels(**label)

        self.running = gauge("vllm:num_requests_running", "running requests")
        self.waiting = gauge("vllm:num_requests_waiting", "waiting requests")
        self.swapped = gauge(
            "vllm:num_requests_swapped", "sequences with KV parked host-side"
        )
        self.preemptions = counter(
            "vllm:num_preemptions", "recompute preemptions"
        )
        self.cache_usage = gauge(
            "vllm:gpu_cache_usage_perc", "KV page usage (HBM)"
        )
        self.hit_rate = gauge(
            "vllm:gpu_prefix_cache_hit_rate", "prefix cache hit rate"
        )
        self.hits = gauge(
            "vllm:gpu_prefix_cache_hits_total", "prefix cache hit tokens"
        )
        self.queries = gauge(
            "vllm:gpu_prefix_cache_queries_total", "prefix cache query tokens"
        )
        self.prompt_tokens = counter(
            "vllm:prompt_tokens_total", "prompt tokens processed"
        )
        self.generation_tokens = counter(
            "vllm:generation_tokens_total", "tokens generated"
        )
        self.ttft = hist(
            "vllm:time_to_first_token_seconds",
            "TTFT",
            (0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4),
        )
        self.e2e = hist(
            "vllm:e2e_request_latency_seconds",
            "request latency",
            (0.1, 0.25, 0.5, 1, 2, 4, 8, 16, 32, 64),
        )
        self.success = counter("vllm:request_success_total", "finished requests")
        # Counters (not gauges): the _total suffix promises monotonic
        # counter semantics — rate()/increase() queries and counter-typed
        # dashboards break across restarts otherwise (vLLM exports these as
        # Counters). The engine reports cumulative totals, so refresh()
        # inc()s by delta.
        self.spec_draft = counter(
            "vllm:spec_decode_num_draft_tokens",
            "speculative draft tokens proposed",
        )
        self.spec_accepted = counter(
            "vllm:spec_decode_num_accepted_tokens",
            "speculative draft tokens accepted",
        )
        self.pipelined_bursts = counter(
            "pst:pipelined_bursts",
            "decode bursts dispatched as part of an overlapped pipeline "
            "(one burst in flight, host bookkeeping off the critical path)",
        )
        self.decode_dispatches = counter(
            "pst:decode_dispatches",
            "decode bursts dispatched, synchronous or pipelined: the "
            "denominator of pst:pipelined_bursts",
        )
        # A looped stack (PERF.md §3): layers run by the host's count, and
        # the layers of pages under them.
        self.decode_layer_passes = counter(
            "pst:decode_layer_passes",
            "layers run by decode dispatches: the stack's passes x its "
            "layers x the burst's depth, summed over dispatches",
        )
        # Rows behind one prompt hold the same leading pages, and the decode
        # kernel reads those once a call (PERF.md §3): spared / context is
        # the share of a full-attention layer's context reads that did not
        # happen.
        self.decode_context_tokens = counter(
            "pst:decode_context_tokens",
            "context tokens the rows of decode dispatches held, a step of "
            "a burst each: what a layer that attends to the whole context "
            "reads on a walk a row",
        )
        self.decode_shared_tokens_spared = counter(
            "pst:decode_shared_tokens_spared",
            "of those, the tokens such a layer's decode calls did not read "
            "because their rows held them in common pages, read once a "
            "call: (sharing rows - calls) x shared tokens x the burst's "
            "depth; 0 where the calls walk a row (the gather reference, a "
            "shape or a window the kernel's shared phase does not take)",
        )
        self.prefill_layer_passes = counter(
            "pst:prefill_layer_passes",
            "layers run by prefill steps: the stack's passes x its layers, "
            "summed over steps",
        )
        self.kv_slot_layers = gauge(
            "pst:kv_slot_layers",
            "layers of pages a token holds: the layers with a cache, times "
            "the passes of a looped stack",
        )
        self.prefix_waits = counter(
            "pst:prefix_waits",
            "admission attempts held back a step because a running row was "
            "computing the pages the sequence needs next (arrivals behind "
            "one uncached prefix compute it once)",
        )
        # A model with recurrent layers and an expert share (PERF.md §3).
        self.state_slots_in_use = gauge(
            "pst:state_slots_in_use",
            "recurrent-state slots held by sequences (models with "
            "state-space layers)",
        )
        self.state_slot_waits = counter(
            "pst:state_slot_waits",
            "admissions left queued because every recurrent-state slot "
            "was held",
        )
        # Page groups and the skipped cross-decoder (PERF.md §3): the global
        # group for every model, the rest for one whose window layers keep
        # a group of their own.
        self.kv_pages_in_use = gauge(
            "pst:kv_pages_in_use",
            "KV pages held by sequences or the prefix cache, by page group",
            by=("group",),
        )
        self.window_page_steps = counter(
            "pst:window_page_steps",
            "window-group pages held by the rows of each step, summed over "
            "steps",
        )
        self.window_whole_context_page_steps = counter(
            "pst:window_whole_context_page_steps",
            "window-group pages the same rows would hold were every page "
            "kept for the whole context, summed over steps",
        )
        self.window_pages_released = counter(
            "pst:window_pages_released",
            "window-group pages released because their sequence moved past "
            "them (below its sliding window)",
        )
        # The window group's prefix cache (a model whose window layers'
        # pages are matched again by their hashes).
        self.window_pages_cached = gauge(
            "pst:window_pages_cached",
            "unreferenced window-group pages kept under their hashes for a "
            "later prefix match",
        )
        self.window_prefix_tokens_lost = counter(
            "pst:window_prefix_tokens_lost",
            "prompt tokens a prefix match was cut back by because the window "
            "group no longer held the pages under the last window before the "
            "match point",
        )
        self.window_pages_evicted = counter(
            "pst:window_pages_evicted",
            "hashed window-group pages evicted from the group's LRU to make "
            "room",
        )
        self.prefill_tokens = counter(
            "pst:prefill_tokens",
            "tokens prefill steps computed (every layer that runs on every "
            "token)",
        )
        self.prefill_bucket_positions = counter(
            "pst:prefill_bucket_positions",
            "positions the prefill steps' buckets hold (rows x chunk, "
            "padding included)",
        )
        self.cross_decoder_positions = counter(
            "pst:cross_decoder_positions",
            "positions fetched prefill steps ran the cross-decoder on, "
            "counted on the device from the batch it was handed (a model "
            "that skips it for all but each row's last position)",
        )
        self.moe_pairs_routed = counter(
            "pst:moe_pairs_routed",
            "token-expert pairs the router chose, over real tokens and "
            "expert layers of fetched steps",
        )
        self.moe_pairs_held = counter(
            "pst:moe_pairs_held",
            "of pst:moe_pairs_routed, the pairs whose expert this engine "
            "holds (its expert-parallel share)",
        )
        self.moe_busiest_expert_pairs = counter(
            "pst:moe_busiest_expert_pairs",
            "per step and expert layer, the pairs at the busiest held "
            "expert, summed",
        )
        self.moe_experts_touched = counter(
            "pst:moe_experts_touched",
            "per step and expert layer, the held experts that got a pair "
            "(whose weights the grouped products read), summed",
        )
        self.moe_layer_steps = counter(
            "pst:moe_layer_steps",
            "expert layers evaluated by fetched steps: the denominator of "
            "a mean a layer and step over the pst:moe_* counters",
        )
        self.moe_dispatch_overflow = counter(
            "pst:moe_dispatch_overflow",
            "of pst:moe_layer_steps, the expert layers whose held pairs "
            "passed the dispatch's row capacity, so that further rounds "
            "ran (models/moe_dispatch.py::capacity)",
        )
        self.mtp_steps = counter(
            "pst:mtp_steps",
            "verify-and-draft steps fetched (--speculative-mtp): one launch "
            "and one fetch each, counted on the device",
        )
        self.mtp_row_steps = counter(
            "pst:mtp_row_steps",
            "live rows of the verify-and-draft steps fetched, summed: the "
            "denominator of tokens a row and step",
        )
        self.mtp_tokens_emitted = counter(
            "pst:mtp_tokens_emitted",
            "tokens the verify-and-draft steps yielded by the device's "
            "accept test: one a live row, two where its draft was accepted",
        )
        self.mla_prefill_steps = counter(
            "pst:mla_prefill_steps",
            "fetched prefill steps of a latent-attention model, by the way "
            "the chunk attended over its pages (expanded or absorbed)",
            by=("path",),
        )
        self.pipeline_breaks = counter(
            "pst:pipeline_breaks",
            "decode pipelines drained, by why the burst in flight could "
            "not chain",
            by=("reason",),
        )
        self.chain_kept_prefills = counter(
            "pst:chain_kept_prefills",
            "prefill steps a decode chain went on behind without a drain: "
            "the rows they completed joined the chain on the device",
        )
        # Deadline shedding by stage (docs/resilience.md): admission counts
        # at the HTTP layer; queued/running refresh from scheduler stats.
        self.deadline_shed_admission = counter(
            "pst:deadline_shed_admission",
            "requests shed at HTTP admission (budget already expired)",
        )
        self.deadline_shed_queued = counter(
            "pst:deadline_shed_queued",
            "queued sequences shed before consuming a prefill step",
        )
        self.deadline_shed_running = counter(
            "pst:deadline_shed_running",
            "running sequences shed between decode steps",
        )
        self.swap_out = counter(
            "pst:kv_swap_out", "sequences swapped out (KV parked)"
        )
        self.swap_in = counter(
            "pst:kv_swap_in", "sequences swapped back in (KV resumed)"
        )
        self.swap_tail_pages = counter(
            "pst:kv_swap_tail_pages",
            "uncommitted tail pages physically moved by swap",
        )
        self.swap_fallback = counter(
            "pst:kv_swap_fallback_recompute",
            "swap-ins that degraded to recompute (committed pages lost)",
        )
        self.swap_stash = gauge(
            "pst:kv_swap_stash_blocks", "host-DRAM stash occupancy (pages)"
        )
        # Streamed disagg KV handoff (docs/disagg.md): pages shipped to
        # the remote store per prefill chunk, pages staged by the decode
        # side's manifest-following prefetch, and transfers that degraded
        # to the fused path (manifest timeout / kvserver death).
        self.kv_published_blocks = counter(
            "pst:kv_published_blocks",
            "KV pages published to the remote store by the streamed "
            "disagg handoff (per prefill chunk, batched)",
        )
        self.kv_prefetched_blocks = counter(
            "pst:kv_prefetched_blocks",
            "KV pages prefetched from a disagg prefill's manifest while "
            "the prefill was still running",
        )
        self.kv_transfer_fallbacks = counter(
            "pst:kv_transfer_fallbacks",
            "disagg transfers that degraded to the fused path "
            "(manifest timeout or kvserver failure)",
        )
        self.kv_remote_retries = counter(
            "pst:kv_remote_retries",
            "remote-KV GET attempts retried after a transient shard "
            "error (bounded, jittered — docs/kvserver.md)",
        )
        # Tenant QoS (docs/multi-tenancy.md): per-tier queue age is the
        # starvation signal the flood-isolation guarantee asserts on, and
        # batch preemptions count pages reclaimed for interactive work.
        self.tenant_queue_age_interactive = gauge(
            "pst:tenant_queue_age_interactive_seconds",
            "oldest interactive-tier queued sequence's wait (seconds)",
        )
        self.tenant_queue_age_batch = gauge(
            "pst:tenant_queue_age_batch_seconds",
            "oldest batch-tier queued sequence's wait (seconds)",
        )
        self.tenant_batch_preemptions = counter(
            "pst:tenant_batch_preemptions",
            "batch-tier sequences preempted (swap/shed) so a waiting "
            "interactive sequence could admit",
        )
        self._counter_last: dict = {}

    def _counter_to(self, c, key: str, total: float) -> None:
        last = self._counter_last.get(key, 0.0)
        if total > last:
            c.inc(total - last)
            self._counter_last[key] = total
        elif total < last:
            # Engine-side cumulative stat reset in-process: counting
            # restarted from 0, so everything counted since the reset is
            # `total`. Export it and re-baseline, instead of freezing until
            # the total re-exceeds the stale high-water mark.
            if total > 0:
                c.inc(total)
            self._counter_last[key] = total

    def refresh(self, stats: dict) -> None:
        self.running.set(stats["num_requests_running"])
        self.waiting.set(stats["num_requests_waiting"])
        self.swapped.set(
            stats.get("num_requests_swapped", stats["num_preemptions_total"])
        )
        self._counter_to(
            self.preemptions, "preempt", stats["num_preemptions_total"]
        )
        self._counter_to(
            self.swap_out, "swap_out", stats.get("kv_swap_out_total", 0)
        )
        self._counter_to(
            self.swap_in, "swap_in", stats.get("kv_swap_in_total", 0)
        )
        self._counter_to(
            self.swap_tail_pages, "swap_tail",
            stats.get("kv_swap_tail_pages_total", 0),
        )
        self._counter_to(
            self.swap_fallback, "swap_fallback",
            stats.get("kv_swap_fallback_recompute_total", 0),
        )
        self.swap_stash.set(stats.get("kv_swap_stash_blocks", 0))
        self.cache_usage.set(stats["kv_cache_usage_perc"])
        self.hit_rate.set(stats["prefix_cache_hit_rate"])
        self.hits.set(stats["prefix_cache_hits_total"])
        self.queries.set(stats["prefix_cache_queries_total"])
        self._counter_to(
            self.spec_draft, "draft",
            stats.get("spec_decode_num_draft_tokens_total", 0),
        )
        self._counter_to(
            self.spec_accepted, "accepted",
            stats.get("spec_decode_num_accepted_tokens_total", 0),
        )
        self._counter_to(
            self.pipelined_bursts, "pipelined",
            stats.get("pipelined_bursts_total", 0),
        )
        self._counter_to(
            self.decode_dispatches, "decode_dispatches",
            stats.get("decode_dispatches_total", 0),
        )
        self.kv_slot_layers.set(stats.get("kv_slot_layers", 0))
        self.state_slots_in_use.set(stats.get("state_slots_in_use", 0))
        self.kv_pages_in_use("global").set(stats.get("kv_pages_in_use", 0))
        if "window_pages_in_use" in stats:
            self.kv_pages_in_use("window").set(stats["window_pages_in_use"])
            self.window_pages_cached.set(stats["window_pages_cached"])
        for metric, key in (
            (self.state_slot_waits, "state_slot_waits_total"),
            (self.decode_layer_passes, "decode_layer_passes_total"),
            (self.decode_context_tokens, "decode_context_tokens_total"),
            (self.decode_shared_tokens_spared,
             "decode_shared_tokens_spared_total"),
            (self.prefix_waits, "prefix_waits_total"),
            (self.prefill_layer_passes, "prefill_layer_passes_total"),
            (self.window_pages_released, "window_pages_released_total"),
            (self.window_prefix_tokens_lost,
             "window_prefix_tokens_lost_total"),
            (self.window_pages_evicted, "window_pages_evicted_total"),
            (self.window_page_steps, "window_page_steps_total"),
            (self.window_whole_context_page_steps,
             "window_whole_context_page_steps_total"),
            (self.prefill_tokens, "prefill_tokens_total"),
            (self.prefill_bucket_positions, "prefill_bucket_positions_total"),
            (self.cross_decoder_positions, "cross_decoder_positions_total"),
            (self.moe_pairs_routed, "moe_pairs_routed_total"),
            (self.moe_pairs_held, "moe_pairs_held_total"),
            (self.moe_busiest_expert_pairs, "moe_busiest_expert_pairs_total"),
            (self.moe_experts_touched, "moe_experts_touched_total"),
            (self.moe_layer_steps, "moe_layer_steps_total"),
            (self.moe_dispatch_overflow, "moe_dispatch_overflow_total"),
            (self.mtp_steps, "mtp_steps_total"),
            (self.mtp_row_steps, "mtp_row_steps_total"),
            (self.mtp_tokens_emitted, "mtp_tokens_emitted_total"),
        ):
            self._counter_to(metric, key, stats.get(key, 0))
        for path in ("expanded", "absorbed"):
            key = f"mla_prefill_steps_{path}_total"
            if key in stats:
                self._counter_to(self.mla_prefill_steps(path), key, stats[key])
        for why, total in stats.get("pipeline_breaks_total", {}).items():
            self._counter_to(
                self.pipeline_breaks(why), f"pipeline_breaks:{why}", total
            )
        self._counter_to(
            self.chain_kept_prefills, "chain_kept_prefills",
            stats.get("chain_kept_prefills_total", 0),
        )
        self._counter_to(
            self.deadline_shed_queued, "dl_queued",
            stats.get("deadline_sheds_queued_total", 0),
        )
        self._counter_to(
            self.deadline_shed_running, "dl_running",
            stats.get("deadline_sheds_running_total", 0),
        )
        self._counter_to(
            self.kv_published_blocks, "kv_pub",
            stats.get("kv_published_blocks_total", 0),
        )
        self._counter_to(
            self.kv_prefetched_blocks, "kv_prefetch",
            stats.get("kv_prefetched_blocks_total", 0),
        )
        self._counter_to(
            self.kv_transfer_fallbacks, "kv_fallback",
            stats.get("kv_transfer_fallbacks_total", 0),
        )
        self._counter_to(
            self.kv_remote_retries, "kv_retry",
            stats.get("kv_remote_retries_total", 0),
        )
        self.tenant_queue_age_interactive.set(
            stats.get("tenant_queue_age_interactive", 0.0)
        )
        self.tenant_queue_age_batch.set(
            stats.get("tenant_queue_age_batch", 0.0)
        )
        self._counter_to(
            self.tenant_batch_preemptions, "tenant_batch_preempt",
            stats.get("tenant_batch_preemptions_total", 0),
        )


def _kv_transfer_params(req) -> Optional[dict]:
    """The request's ``kv_transfer_params`` (the router's disagg handoff
    stamp, pydantic ``extra="allow"``), validated to a request-id-bearing
    dict — anything else is ignored rather than 400d, mirroring the
    reference connector's permissive surface."""
    raw = getattr(req, "kv_transfer_params", None)
    if not isinstance(raw, dict) or not raw.get("request_id"):
        return None
    return {
        "request_id": str(raw["request_id"]),
        "role": str(raw["role"]) if raw.get("role") else None,
    }


def _parse_logit_bias(raw) -> tuple:
    """OpenAI logit_bias keys are stringified token ids; a non-numeric key
    must surface as a 400, not a 500 (callers catch ValueError). Values are
    validated to OpenAI's documented [-100, 100] range — unbounded biases
    can force tokens users only meant to discourage."""
    if not raw:
        return ()
    try:
        parsed = tuple((int(k), float(v)) for k, v in raw.items())
    except (TypeError, ValueError):
        raise ValueError("logit_bias keys must be integer token ids")
    for _, v in parsed:
        if not (-100.0 <= v <= 100.0):
            raise ValueError(
                "logit_bias values must be in [-100, 100]"
            )
    return parsed


def _parse_guided_choice(raw, tok) -> tuple:
    """Tokenize guided_choice strings (no special tokens — the choices are
    output continuations). Invalid shapes 400 via ValueError."""
    if not raw:
        return ()
    if tok is None:
        raise ValueError("guided_choice is not supported on this endpoint")
    if not isinstance(raw, list) or not all(
        isinstance(c, str) and c for c in raw
    ):
        raise ValueError("guided_choice must be a list of non-empty strings")
    if len(raw) > 64:
        raise ValueError("guided_choice supports at most 64 choices")
    choices = []
    for c in raw:
        ids = tuple(tok.encode(c, add_special_tokens=False))
        if not ids or len(ids) > 256:
            raise ValueError(
                f"guided_choice entry tokenizes to {len(ids)} tokens "
                "(must be 1..256)"
            )
        choices.append(ids)
    return tuple(choices)


def build_sampling(
    req, max_model_len: int, prompt_len: int, tok=None
) -> SamplingParams:
    limit = max(max_model_len - prompt_len - 1, 1)
    want = req.max_completion_tokens or req.max_tokens
    # OpenAI shapes: completions carry an int `logprobs` (top-N count);
    # chat carries bool `logprobs` + int `top_logprobs` (0 is valid: chosen
    # token's logprob only, no alternatives).
    lp = getattr(req, "logprobs", None)
    if isinstance(lp, bool):
        if lp:
            top = getattr(req, "top_logprobs", None)
            lp = int(top) if top is not None else 0
        else:
            lp = None
    gc = _parse_guided_choice(getattr(req, "guided_choice", None), tok)
    return SamplingParams(
        max_tokens=min(want, limit) if want else limit,
        temperature=req.temperature,
        top_p=req.top_p,
        top_k=req.top_k,
        min_p=req.min_p,
        stop=req.stop,
        stop_token_ids=tuple(req.stop_token_ids or ()),
        # Guided requests terminate via EOS at a completed choice (the
        # prefix-choice escape hatch) — ignore_eos would deadlock the mask.
        ignore_eos=req.ignore_eos and not gc,
        seed=req.seed,
        presence_penalty=req.presence_penalty,
        frequency_penalty=req.frequency_penalty,
        repetition_penalty=req.repetition_penalty,
        logprobs=int(lp) if lp is not None else None,
        logit_bias=_parse_logit_bias(getattr(req, "logit_bias", None)),
        guided_choice=gc,
    )


def _fmt_completion_logprobs(tok, entries, echo_ids=None, base_offset=0):
    """OpenAI completions `logprobs` object. Echoed prompt tokens carry null
    logprobs (the engine does not keep prefill logits; same shape as the
    API's null-first-token convention). ``base_offset`` anchors text_offset
    into the FULL accumulated completion text for streaming chunks."""
    tokens, token_lps, top_lps, offsets = [], [], [], []
    off = base_offset
    for tid in echo_ids or []:
        s = tok.decode([tid])
        tokens.append(s)
        token_lps.append(None)
        top_lps.append(None)
        offsets.append(off)
        off += len(s)
    for e in entries:
        s = tok.decode([e["token_id"]])
        tokens.append(s)
        token_lps.append(e["logprob"])
        top_lps.append({tok.decode([t]): lp for t, lp in e["top"]})
        offsets.append(off)
        off += len(s)
    return {
        "tokens": tokens,
        "token_logprobs": token_lps,
        "top_logprobs": top_lps,
        "text_offset": offsets,
    }


def _fmt_chat_logprobs(tok, entries):
    """OpenAI chat `logprobs.content` entries."""
    def one(tid, lp):
        s = tok.decode([tid])
        return {"token": s, "logprob": lp, "bytes": list(s.encode())}

    return {
        "content": [
            dict(
                one(e["token_id"], e["logprob"]),
                top_logprobs=[one(t, lp) for t, lp in e["top"]],
            )
            for e in entries
        ]
    }


def create_engine_app(
    engine: AsyncLLMEngine,
    api_key: Optional[str] = None,
    cross_encoder=None,
    tracing: bool = True,
    debug_requests_buffer: int = 256,
    profiling: bool = False,
    profile_dir: str = "/tmp/pst_profiles",
    profile_max_ms: float = 60_000.0,
) -> web.Application:
    # Everything except unauthenticated probe/scrape endpoints is guarded
    # when --api-key is set (/sleep in particular is destructive). Enforced
    # as a middleware so no handler can be forgotten.
    # /debug/requests is deliberately NOT open: timelines carry
    # per-request metadata (request ids, backend URLs, error strings) —
    # when an api key is configured it is guarded like the work endpoints.
    _OPEN_PATHS = {
        "/health", "/ready", "/metrics", "/version", "/is_sleeping",
        "/is_draining",
    }

    # Paths that get a root span + timeline entry (the work the router
    # proxies; admin/probe endpoints are not traced).
    _TRACED_PATHS = {
        "/v1/chat/completions", "/v1/completions", "/v1/embeddings",
        "/rerank", "/v1/rerank", "/v2/rerank", "/score", "/v1/score",
    }

    recorder = SpanRecorder(
        "engine", buffer=debug_requests_buffer, enabled=tracing
    )

    @web.middleware
    async def tracing_middleware(request: web.Request, handler):
        """Root span per generation request, joining the router's trace via
        the propagated W3C ``traceparent``; ``X-Request-Id`` (the router's
        id, or a fresh one) lands on every unprepared response —
        including 503 drain and 504 deadline sheds."""
        if not (
            recorder.enabled
            and request.method == "POST"
            and request.path in _TRACED_PATHS
        ):
            return await handler(request)
        request_id = request.headers.get("X-Request-Id") or random_id("req")
        trace = recorder.trace(
            request_id,
            headers=request.headers,
            name="engine_request",
            attributes={"http.target": request.path},
        )
        request["trace"] = trace
        request["request_id"] = request_id
        # Structured-log correlation: engine log lines under this request
        # carry the SAME trace id the router's lines do (the propagated
        # traceparent joined the trace above), plus the router-stamped
        # tenant — one grep spans the whole hop chain.
        log_token = bind_log_context(
            request_id=request_id,
            trace_id=trace.trace_id,
            tenant=request.headers.get("X-PST-Tenant"),
        )
        status: Optional[int] = None
        try:
            response = await handler(request)
            status = response.status
            if not response.prepared:
                response.headers.setdefault("X-Request-Id", request_id)
            return response
        finally:
            unbind_log_context(log_token)
            trace.finish(status=status)

    @web.middleware
    async def auth_middleware(request: web.Request, handler):
        if api_key is not None and request.path not in _OPEN_PATHS:
            auth = request.headers.get("Authorization", "")
            if auth != f"Bearer {api_key}":
                return _error("invalid API key", 401, "authentication_error")
        return await handler(request)

    app = web.Application(middlewares=[tracing_middleware, auth_middleware])
    model_name = engine.engine.model_name
    metrics = EngineMetrics(model_name)
    app["engine"] = engine
    app["metrics"] = metrics
    app["span_recorder"] = recorder

    def _record_engine_stages(
        request: web.Request,
        queue_time: Optional[float],
        prefill_time: Optional[float],
        decode_time: Optional[float],
        deliver_time: Optional[float] = None,
    ) -> None:
        """Replay the Sequence's TTFT decomposition as spans: queue wait →
        prefill → decode, laid back-to-back ending now, and ``deliver``
        from where prefill ends: the first token's way from the step
        thread's stamp to the return of the socket write that carried it
        (the hand-over to the event loop, its turn, the queue, text to
        JSON, the write). Post-hoc so the step thread never touches the
        recorder."""
        trace = request.get("trace")
        if trace is None:
            return
        now = time.monotonic()
        end_prefill = now - (decode_time or 0.0)
        end_queue = end_prefill - (prefill_time or 0.0)
        if queue_time is not None:
            trace.record_span("engine_queue", queue_time, end_mono=end_queue)
        if prefill_time is not None:
            trace.record_span("prefill", prefill_time, end_mono=end_prefill)
        if deliver_time is not None:
            trace.record_span(
                "deliver", deliver_time,
                end_mono=end_prefill + max(deliver_time, 0.0),
            )
        if decode_time is not None:
            trace.record_span("decode", decode_time, end_mono=now)

    async def _send_json(
        request: web.Request, payload: dict, headers: dict
    ) -> web.Response:
        """A JSON response whose whole body is written here, not after the
        handler returns: the caller stamps the write's return."""
        resp = web.json_response(payload, headers=headers)
        await resp.prepare(request)
        await resp.write_eof()
        return resp

    def _attach_compile_events(request: web.Request, events) -> None:
        """Surface the XLA compiles a step absorbed as `compile` span
        events on the victim request's trace, so a mid-run recompile is
        attributable from the timeline of the request it delayed."""
        trace = request.get("trace")
        if trace is None or not events:
            return
        for ev in events:
            trace.add_event("compile", **ev)

    def _lora_names() -> List[str]:
        mgr = engine.engine.lora_manager
        return [a.name for a in mgr.list_adapters()] if mgr else []

    def _resolve_lora(requested_model: str) -> Optional[str]:
        """Request model == a loaded adapter name → serve under that LoRA."""
        if requested_model and requested_model != model_name:
            mgr = engine.engine.lora_manager
            if mgr is not None and mgr.get(requested_model) is not None:
                return requested_model
        return None

    def _request_deadline(request: web.Request):
        """``(error_response, deadline)``: parse the router-propagated
        ``X-PST-Deadline-Ms`` budget. Already expired → instant 504 (the
        cheapest shed point — no tokenization, no scheduler admission);
        otherwise the monotonic expiry to carry on the Sequence so the
        scheduler can shed it if the budget dies while queued/running."""
        if not engine.engine.cfg.deadline_shedding:
            return None, None
        d = parse_deadline(request.headers)
        if d is None:
            return None, None
        if d.expired():
            metrics.deadline_shed_admission.inc()
            trace = request.get("trace")
            if trace is not None:
                trace.add_event("deadline_shed", stage="engine_admission")
            return _deadline_error(), None
        return None, d.expires_at

    def _request_tenant(request: web.Request):
        """``(tenant, tenant_class)`` from the router-stamped headers
        (docs/multi-tenancy.md). The router overwrites client-sent values
        at admission, so within a deployed stack these are trusted; an
        engine reached directly treats the caller as the default
        interactive tenant unless it self-declares."""
        if not engine.engine.cfg.tenant_fairness:
            return None, None
        tenant = request.headers.get("X-PST-Tenant")
        tier = request.headers.get("X-PST-Tenant-Class")
        return tenant, tier

    # -- model listing -------------------------------------------------

    async def list_models(request: web.Request) -> web.Response:
        now = int(time.time())
        data = [
            {"id": model_name, "object": "model", "created": now,
             "owned_by": "production-stack-tpu", "root": None, "parent": None}
        ] + [
            {"id": a, "object": "model", "created": now,
             "owned_by": "production-stack-tpu", "root": None, "parent": model_name}
            for a in _lora_names()
        ]
        return web.json_response({"object": "list", "data": data})

    # -- generation ----------------------------------------------------

    async def chat_completions(request: web.Request) -> web.StreamResponse:
        try:
            req = ChatCompletionRequest(**await request.json())
        except Exception as e:  # noqa: BLE001
            return _error(f"invalid request body: {e}")
        if engine.sleeping:
            return _error("engine is sleeping", 503, "service_unavailable")
        if engine.draining:
            return _drain_error()
        if engine.warming:
            return _warming_error()
        # continue_final_message (vLLM parity, pydantic extra="allow"):
        # render the final message's turn OPEN so generation continues it
        # instead of starting a fresh assistant turn — what the router's
        # stream-resume continuation requests rely on.
        cfm = bool(getattr(req, "continue_final_message", False))
        prompt = engine.engine.tokenizer.apply_chat_template(
            req.messages, add_generation_prompt=not cfm,
            continue_final_message=cfm,
        )
        return await _serve_generation(request, req, prompt, is_chat=True)

    async def completions(request: web.Request) -> web.StreamResponse:
        try:
            req = CompletionRequest(**await request.json())
        except Exception as e:  # noqa: BLE001
            return _error(f"invalid request body: {e}")
        if engine.sleeping:
            return _error("engine is sleeping", 503, "service_unavailable")
        if engine.draining:
            return _drain_error()
        if engine.warming:
            return _warming_error()
        prompt = req.prompt
        # Normalize the four OpenAI prompt forms: str, [str, ...],
        # [int, ...] (one tokenized prompt), [[int, ...], ...] (a batch).
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            prompt = [prompt]
        prompts = prompt if isinstance(prompt, list) else [prompt]
        if not prompts:
            return _error("prompt must not be empty")
        if len(prompts) == 1:
            p = prompts[0]
            if isinstance(p, list):
                return await _serve_generation(
                    request, req, None, is_chat=False, prompt_ids=p
                )
            return await _serve_generation(request, req, str(p), is_chat=False)
        if req.stream:
            return _error("streaming is not supported for batched prompts")
        if (req.n or 1) > 1 or (req.best_of or 1) > 1:
            # Explicit rejection beats silently returning one unranked
            # sample per prompt.
            return _error("n/best_of > 1 is not supported for batched prompts")
        return await _serve_completion_batch(request, req, prompts)

    async def _serve_completion_batch(
        request: web.Request, req, prompts: List
    ) -> web.Response:
        """OpenAI batched completions: one choice per prompt, index-aligned."""
        tok = engine.engine.tokenizer
        max_len = engine.engine.cfg.max_model_len
        err, deadline = _request_deadline(request)
        if err is not None:
            return err
        created = int(time.time())
        rid = random_id("cmpl")
        start = time.time()
        tenant, tenant_class = _request_tenant(request)

        async def one(prompt) -> dict:
            if isinstance(prompt, list):
                try:
                    ids = [int(x) for x in prompt]
                except (TypeError, ValueError):
                    return {"error": "prompt token ids must be integers", "ids": []}
            else:
                ids = tok.encode(str(prompt))
            if len(ids) >= max_len:
                return {"error": f"prompt has {len(ids)} tokens (max {max_len})",
                        "ids": ids}
            try:
                sampling = build_sampling(req, max_len, len(ids), tok)
            except ValueError as e:
                return {"error": str(e), "ids": ids}
            parts, n_out, finish = [], 0, None
            async for out in engine.generate(
                prompt_token_ids=ids, sampling=sampling, deadline=deadline,
                tenant=tenant, tenant_class=tenant_class,
            ):
                parts.append(out.text_delta)
                n_out = out.num_output_tokens
                finish = out.finish_reason or finish
                if out.num_output_tokens == 1 and out.ttft is not None:
                    metrics.ttft.observe(out.ttft)
            return {"text": "".join(parts), "n_in": len(ids), "n_out": n_out,
                    "finish": finish}

        results = await asyncio.gather(*(one(p) for p in prompts))
        if any("error" in r for r in results):
            return _error(next(r["error"] for r in results if "error" in r))
        if any(r.get("finish") == "deadline" for r in results):
            # The budget died while part of the batch was still queued or
            # decoding: the batch cannot complete within its deadline.
            return _deadline_error()
        usage = {
            "prompt_tokens": sum(r["n_in"] for r in results),
            "completion_tokens": sum(r["n_out"] for r in results),
            "total_tokens": sum(r["n_in"] + r["n_out"] for r in results),
        }
        metrics.e2e.observe(time.time() - start)
        metrics.success.inc()
        metrics.prompt_tokens.inc(usage["prompt_tokens"])
        metrics.generation_tokens.inc(usage["completion_tokens"])
        return web.json_response(
            {
                "id": rid, "object": "text_completion", "created": created,
                "model": req.model,
                "choices": [
                    {"index": i, "text": r["text"], "logprobs": None,
                     "finish_reason": r["finish"]}
                    for i, r in enumerate(results)
                ],
                "usage": usage,
            },
            headers={"X-Request-Id": rid},
        )

    async def _serve_generation(
        request: web.Request,
        req,
        prompt: Optional[str],
        is_chat: bool,
        prompt_ids: Optional[List[int]] = None,
    ) -> web.StreamResponse:
        t_admission = time.monotonic()
        tok = engine.engine.tokenizer
        if prompt_ids is not None:
            try:  # malformed ids must 400 here, not poison the step thread
                ids = [int(x) for x in prompt_ids]
            except (TypeError, ValueError):
                return _error("prompt token ids must be integers")
        else:
            ids = tok.encode(prompt or "")
        max_len = engine.engine.cfg.max_model_len
        if len(ids) >= max_len:
            return _error(
                f"prompt has {len(ids)} tokens, exceeds max_model_len={max_len}"
            )
        if not engine.engine.scheduler.prompt_fits(len(ids)):
            # Scheduler.add's feasibility guard at the HTTP layer (shared
            # helper) so the client sees a 400, not an engine-thread error.
            return _error(
                f"prompt of {len(ids)} tokens needs more KV pages than the "
                f"engine has ({engine.engine.allocator.num_blocks})"
            )
        try:
            sampling = build_sampling(req, max_len, len(ids), tok)
        except ValueError as e:
            return _error(str(e))
        err, deadline = _request_deadline(request)
        if err is not None:
            return err
        trace = request.get("trace")
        if trace is not None:
            # Tokenization + validation + budget parse = engine admission.
            trace.record_span(
                "engine_admission", time.monotonic() - t_admission,
                attributes={"prompt_tokens": len(ids)},
            )
        rid = random_id("chatcmpl" if is_chat else "cmpl")
        created = int(time.time())
        start = time.time()
        obj = "chat.completion.chunk" if is_chat else "text_completion"
        n_choices = max(int(getattr(req, "n", 1) or 1), 1)
        # best_of is a completions-only OpenAI field; on chat it would be
        # an unvalidated extra (pydantic extra=\"allow\") — ignore it there
        # like every other unknown field.
        best_of = n_choices if is_chat else int(req.best_of or n_choices)
        if best_of < n_choices:
            return _error("best_of must be >= n")
        # best_of caps at 20 (OpenAI parity); n caps at 128 (OpenAI's own n
        # ceiling) — both double as this server's per-request fan-out bound.
        if best_of > 20 and best_of > n_choices:
            return _error("best_of must be <= 20")
        if n_choices > 128 or best_of > 128:
            return _error("n must be <= 128")
        echo = bool(getattr(req, "echo", False)) and not is_chat
        want_lp = sampling.logprobs is not None
        lora = _resolve_lora(getattr(req, "model", ""))

        if n_choices > 1 or best_of > 1:
            if req.stream:
                return _error("streaming with n/best_of > 1 is not supported")
            return await _serve_n_choices(
                request, req, ids, sampling, rid, created, is_chat, n_choices,
                echo, lora, best_of, deadline=deadline,
            )

        tenant, tenant_class = _request_tenant(request)
        kv_transfer = _kv_transfer_params(req)
        if kv_transfer is not None:
            # Consumer leg of a disagg handoff (docs/disagg.md): follow the
            # prefill's manifest and stage published pages in the host pool
            # WHILE the remote prefill still runs; admission proceeds when
            # the completion marker lands — the prompt is then a host-tier
            # prefix hit and the first decode step dispatches immediately.
            # Timeout / dead kvserver → plain admission (fused fallback:
            # this engine recomputes the prefill; no client-visible error).
            prefetcher = engine.engine.kv_prefetcher
            if prefetcher is not None and kv_transfer.get("role") == "consumer":
                t_fetch = time.monotonic()
                fetch = await asyncio.get_running_loop().run_in_executor(
                    None,
                    lambda: prefetcher.prefetch(
                        str(kv_transfer["request_id"]), deadline=deadline
                    ),
                )
                if trace is not None:
                    trace.add_event(
                        "kv_prefetch",
                        complete=fetch["complete"], blocks=fetch["blocks"],
                    )
                observe_stage(
                    "engine", "kv_prefetch", time.monotonic() - t_fetch
                )
        gen = engine.generate(
            prompt_token_ids=ids, sampling=sampling, request_id=rid,
            lora_name=lora, deadline=deadline,
            tenant=tenant, tenant_class=tenant_class,
            kv_transfer=kv_transfer,
        )

        if req.stream:
            resp = web.StreamResponse(status=200)
            resp.headers["Content-Type"] = "text/event-stream"
            resp.headers["Cache-Control"] = "no-cache"
            resp.headers["X-Request-Id"] = rid
            await resp.prepare(request)
            n_out = 0
            last_out = None
            deliver_time = None
            try:
                if is_chat:
                    first = {
                        "id": rid, "object": obj, "created": created,
                        "model": req.model,
                        "choices": [{"index": 0, "delta": {"role": "assistant"},
                                     "finish_reason": None}],
                    }
                    await resp.write(f"data: {json.dumps(first)}\n\n".encode())
                first_chunk = True
                # Running char offset into the accumulated completion text
                # (echo prefix included) so streamed text_offset entries
                # stay globally consistent, not chunk-relative.
                char_off = len(engine.engine.tokenizer.decode(ids)) if echo else 0
                async for out in gen:
                    n_out = out.num_output_tokens
                    last_out = out
                    if out.compile_events:
                        _attach_compile_events(request, out.compile_events)
                    if out.num_output_tokens == 1 and out.ttft is not None:
                        metrics.ttft.observe(out.ttft)
                    lp_obj = None
                    if want_lp and out.logprobs:
                        if is_chat:
                            lp_obj = _fmt_chat_logprobs(
                                engine.engine.tokenizer, out.logprobs
                            )
                        else:
                            lp_obj = _fmt_completion_logprobs(
                                engine.engine.tokenizer, out.logprobs,
                                base_offset=char_off,
                            )
                    if is_chat:
                        delta = {"content": out.text_delta} if out.text_delta else {}
                        choice = {"index": 0, "delta": delta,
                                  "logprobs": lp_obj,
                                  "finish_reason": out.finish_reason}
                    else:
                        text = out.text_delta
                        if echo and first_chunk:
                            text = engine.engine.tokenizer.decode(ids) + text
                        choice = {"index": 0, "text": text,
                                  "logprobs": lp_obj,
                                  "finish_reason": out.finish_reason}
                    char_off += len(out.text_delta)
                    first_chunk = False
                    chunk = {"id": rid, "object": obj, "created": created,
                             "model": req.model, "choices": [choice]}
                    if out.finished and getattr(req, "stream_options", None) and (
                        req.stream_options or {}
                    ).get("include_usage"):
                        chunk["usage"] = {
                            "prompt_tokens": len(ids),
                            "completion_tokens": n_out,
                            "total_tokens": len(ids) + n_out,
                        }
                        # Streams learn their cost only at the end — the
                        # 200 headers are long gone, so the usage chunk
                        # is the streaming cost surface.
                        if out.cost is not None:
                            chunk["usage"]["pst_cost"] = out.cost
                    await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
                    if deliver_time is None and out.first_token_time is not None:
                        deliver_time = time.monotonic() - out.first_token_time
                await resp.write(b"data: [DONE]\n\n")
            except (ConnectionResetError, asyncio.CancelledError):
                await engine.abort(rid)
                raise
            except ValueError as e:
                # Rejected on the engine thread (add-time validation not
                # mirrored by an HTTP precheck). The 200 headers are gone —
                # emit an OpenAI-style error event, then terminate. Abort
                # in case the failure happened mid-stream (the sequence
                # must not keep decoding for a dead client).
                await engine.abort(rid)
                # Stable machine-readable code: an in-band error frame is
                # an engine-*reported* failure (deliberate), which the
                # router's stream journal must never resume — unlike a
                # transport death, which it may.
                err = {"error": {"message": str(e),
                                 "type": "invalid_request_error",
                                 "code": "engine_rejected"}}
                await resp.write(f"data: {json.dumps(err)}\n\n".encode())
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
                return resp
            if last_out is not None:
                _record_engine_stages(
                    request, last_out.queue_time, last_out.prefill_time,
                    last_out.decode_time, deliver_time,
                )
            metrics.e2e.observe(time.time() - start)
            metrics.success.inc()
            metrics.prompt_tokens.inc(len(ids))
            metrics.generation_tokens.inc(n_out)
            await resp.write_eof()
            return resp

        # Non-streaming: accumulate.
        try:
            result = await _collect(gen)
        except asyncio.CancelledError:
            await engine.abort(rid)
            raise
        except ValueError as e:  # engine-thread rejection → HTTP 400
            await engine.abort(rid)
            return _error(str(e))
        if result["finish_reason"] == "deadline":
            # Shed by the scheduler (queued past its budget, or expired
            # mid-decode): nothing useful to return — 504, tagged.
            if trace is not None:
                trace.add_event("deadline_shed", stage="engine_scheduler")
            return _deadline_error()
        _attach_compile_events(request, result.get("compile_events"))
        usage = {
            "prompt_tokens": len(ids),
            "completion_tokens": len(result["token_ids"]),
            "total_tokens": len(ids) + len(result["token_ids"]),
        }
        headers = {"X-Request-Id": rid}
        cost = result.get("cost")
        if cost is not None:
            # Cost attribution (docs/observability.md "Cost attribution"):
            # the request's device-seconds ride the response both as a
            # header (proxied through the router untouched) and as a usage
            # extension, so billing pipelines can consume either.
            usage["pst_cost"] = cost
            headers["X-PST-Cost"] = json.dumps(cost, separators=(",", ":"))
        metrics.e2e.observe(time.time() - start)
        metrics.success.inc()
        metrics.prompt_tokens.inc(len(ids))
        metrics.generation_tokens.inc(len(result["token_ids"]))
        choice = _build_choice(req, result, 0, is_chat, echo, ids)
        payload = {
            "id": rid,
            "object": "chat.completion" if is_chat else "text_completion",
            "created": created, "model": req.model,
            "choices": [choice], "usage": usage,
        }
        resp = await _send_json(request, payload, headers)
        _record_stages_of(request, result)
        return resp

    def _record_stages_of(request: web.Request, result: dict) -> None:
        """The stages of a collected generation whose body has just been
        written: unstreamed, the first token leaves with everything else."""
        first = result["first_token_time"]
        _record_engine_stages(
            request, result["queue_time"], result["prefill_time"],
            result["decode_time"],
            time.monotonic() - first if first is not None else None,
        )

    async def _collect(gen) -> dict:
        """Drain one generation stream into text/tokens/logprobs/finish
        (plus the Sequence's stage timings for span reconstruction)."""
        text_parts: List[str] = []
        token_ids: List[int] = []
        lp_entries: List[dict] = []
        compile_events: List[dict] = []
        finish_reason = None
        cost = None
        queue_time = prefill_time = decode_time = first_token_time = None
        async for out in gen:
            if out.num_output_tokens == 1 and out.ttft is not None:
                metrics.ttft.observe(out.ttft)
            text_parts.append(out.text_delta)
            token_ids.extend(out.new_token_ids)
            if out.logprobs:
                lp_entries.extend(out.logprobs)
            if out.compile_events:
                compile_events.extend(out.compile_events)
            finish_reason = out.finish_reason or finish_reason
            cost = out.cost if out.cost is not None else cost
            queue_time = out.queue_time if out.queue_time is not None else queue_time
            prefill_time = (
                out.prefill_time if out.prefill_time is not None else prefill_time
            )
            decode_time = (
                out.decode_time if out.decode_time is not None else decode_time
            )
            first_token_time = first_token_time or out.first_token_time
        return {
            "text": "".join(text_parts), "token_ids": token_ids,
            "logprobs": lp_entries, "finish_reason": finish_reason,
            "queue_time": queue_time, "prefill_time": prefill_time,
            "decode_time": decode_time, "first_token_time": first_token_time,
            "compile_events": compile_events, "cost": cost,
        }

    def _build_choice(req, result, index, is_chat, echo, prompt_ids) -> dict:
        tok = engine.engine.tokenizer
        lp_obj = None
        if result["logprobs"]:
            if is_chat:
                lp_obj = _fmt_chat_logprobs(tok, result["logprobs"])
            else:
                lp_obj = _fmt_completion_logprobs(
                    tok, result["logprobs"],
                    echo_ids=prompt_ids if echo else None,
                )
        if is_chat:
            return {
                "index": index,
                "message": {"role": "assistant", "content": result["text"]},
                "logprobs": lp_obj,
                "finish_reason": result["finish_reason"],
            }
        text = result["text"]
        if echo:
            text = tok.decode(prompt_ids) + text
        return {"index": index, "text": text, "logprobs": lp_obj,
                "finish_reason": result["finish_reason"]}

    async def _serve_n_choices(
        request, req, ids, sampling, rid, created, is_chat, n_choices, echo,
        lora, best_of=None, deadline=None,
    ) -> web.Response:
        """OpenAI `n` / `best_of`: sample ``best_of`` independent candidates
        of one prompt (the prompt prefix is KV-shared across them via the
        prefix cache); when ``best_of > n``, keep the n candidates with the
        highest mean token logprob (which forces logprobs on internally)."""
        import dataclasses as _dc

        start = time.time()
        n_sample = best_of or n_choices
        rank = n_sample > n_choices

        # Ranking needs per-token logprobs even when the client did not ask
        # for them in the response.
        lp_setting = (
            0 if rank and sampling.logprobs is None else sampling.logprobs
        )

        tenant, tenant_class = _request_tenant(request)

        async def one(i: int) -> dict:
            sp = _dc.replace(
                sampling,
                seed=(sampling.seed + i) if sampling.seed is not None else None,
                logprobs=lp_setting,
            )
            return await _collect(engine.generate(
                prompt_token_ids=ids, sampling=sp, request_id=f"{rid}-{i}",
                lora_name=lora, deadline=deadline,
                tenant=tenant, tenant_class=tenant_class,
            ))

        try:
            results = list(
                await asyncio.gather(*(one(i) for i in range(n_sample)))
            )
        except ValueError as e:
            # One candidate rejected on the engine thread: abort ALL
            # candidates (gather returned on the first failure — siblings
            # are still decoding for a request the client sees fail).
            for i in range(n_sample):
                await engine.abort(f"{rid}-{i}")
            return _error(str(e))
        if any(r["finish_reason"] == "deadline" for r in results):
            return _deadline_error()
        first = results[0]
        _attach_compile_events(request, first.get("compile_events"))
        # OpenAI bills EVERY best_of candidate in completion_tokens.
        sampled_tokens = sum(len(r["token_ids"]) for r in results)
        if rank:
            def mean_lp(r):
                lps = [e["logprob"] for e in r["logprobs"]]
                return sum(lps) / max(len(lps), 1)

            results.sort(key=mean_lp, reverse=True)
            results = results[:n_choices]
            if sampling.logprobs is None:  # client didn't ask: strip
                for r in results:
                    r["logprobs"] = []
        usage = {
            "prompt_tokens": len(ids),
            "completion_tokens": sampled_tokens,
            "total_tokens": len(ids) + sampled_tokens,
        }
        metrics.e2e.observe(time.time() - start)
        metrics.success.inc()
        metrics.prompt_tokens.inc(len(ids))
        metrics.generation_tokens.inc(sampled_tokens)
        payload = {
            "id": rid,
            "object": "chat.completion" if is_chat else "text_completion",
            "created": created, "model": req.model,
            "choices": [
                _build_choice(req, r, i, is_chat, echo, ids)
                for i, r in enumerate(results)
            ],
            "usage": usage,
        }
        resp = await _send_json(request, payload, {"X-Request-Id": rid})
        # Stage decomposition from the first candidate (all candidates
        # share admission and the KV-shared prompt prefill; recording one
        # keeps engine_queue/prefill/decode counts 1:1 with requests).
        _record_stages_of(request, first)
        return resp

    # -- embeddings / rerank / score ----------------------------------

    async def embeddings(request: web.Request) -> web.Response:
        try:
            req = EmbeddingRequest(**await request.json())
        except Exception as e:  # noqa: BLE001
            return _error(f"invalid request body: {e}")
        if engine.draining:
            # Same admission gate as the generation endpoints: encode work
            # accepted after /drain would race the preStop SIGTERM.
            return _drain_error()
        if engine.warming:
            return _warming_error()
        err, _ = _request_deadline(request)
        if err is not None:
            return err
        tok = engine.engine.tokenizer
        inputs = req.input if isinstance(req.input, list) else [req.input]
        if inputs and isinstance(inputs[0], int):
            inputs = [inputs]  # single token-id list
        data = []
        total_tokens = 0
        for i, item in enumerate(inputs):
            ids = item if isinstance(item, list) else tok.encode(str(item))
            total_tokens += len(ids)
            vec = await asyncio.get_event_loop().run_in_executor(
                None, engine.engine.runner.encode, ids
            )
            data.append(
                {"object": "embedding", "index": i, "embedding": vec.tolist()}
            )
        return web.json_response(
            {
                "object": "list", "data": data, "model": req.model,
                "usage": {"prompt_tokens": total_tokens,
                          "total_tokens": total_tokens},
            }
        )

    async def _similarity(texts_a: List[str], texts_b: List[str]) -> List[float]:
        loop = asyncio.get_event_loop()
        tok = engine.engine.tokenizer

        async def emb(t: str):
            return await loop.run_in_executor(
                None, engine.engine.runner.encode, tok.encode(t)
            )

        scores = []
        for a, b in zip(texts_a, texts_b):
            va, vb = await emb(a), await emb(b)
            scores.append(float(np.dot(va, vb)))
        return scores

    # Scoring method surfaced in rerank/score responses. With a
    # --scoring-model loaded (bge-reranker-style checkpoint), (query, doc)
    # pairs are scored JOINTLY by the cross-encoder's classification head —
    # real reranking. Without one, relevance falls back to embedding cosine
    # similarity from the decoder's own hidden states; the explicit label
    # keeps clients from mistaking the approximation for the real thing.
    _SCORING_METHOD = (
        "cross_encoder" if cross_encoder else "embedding_cosine_similarity"
    )

    async def _pair_scores(
        texts_a: List[str], texts_b: List[str]
    ) -> List[float]:
        if cross_encoder is not None:
            return await asyncio.get_event_loop().run_in_executor(
                None, cross_encoder.score_pairs, list(zip(texts_a, texts_b))
            )
        return await _similarity(texts_a, texts_b)

    async def rerank(request: web.Request) -> web.Response:
        if engine.draining:
            return _drain_error()
        if engine.warming:
            return _warming_error()
        err, _ = _request_deadline(request)
        if err is not None:
            return err
        body = await request.json()
        query = body.get("query", "")
        docs = body.get("documents", [])
        top_n = body.get("top_n") or len(docs)
        scores = await _pair_scores([query] * len(docs), docs)
        order = sorted(range(len(docs)), key=lambda i: -scores[i])[:top_n]
        return web.json_response(
            {
                "id": random_id("rerank"),
                "model": body.get("model", model_name),
                "scoring_method": _SCORING_METHOD,
                "results": [
                    {"index": i, "document": {"text": docs[i]},
                     "relevance_score": scores[i]}
                    for i in order
                ],
            }
        )

    async def score(request: web.Request) -> web.Response:
        if engine.draining:
            return _drain_error()
        if engine.warming:
            return _warming_error()
        err, _ = _request_deadline(request)
        if err is not None:
            return err
        body = await request.json()
        t1 = body.get("text_1", "")
        t2 = body.get("text_2", "")
        l1 = t1 if isinstance(t1, list) else [t1]
        l2 = t2 if isinstance(t2, list) else [t2]
        if len(l1) == 1 and len(l2) > 1:
            l1 = l1 * len(l2)
        scores = await _pair_scores(l1, l2)
        return web.json_response(
            {
                "id": random_id("score"),
                "object": "list",
                "model": body.get("model", model_name),
                "scoring_method": _SCORING_METHOD,
                "data": [
                    {"index": i, "object": "score", "score": s}
                    for i, s in enumerate(scores)
                ],
                "usage": {},
            }
        )

    # -- tokenize ------------------------------------------------------

    async def tokenize(request: web.Request) -> web.Response:
        body = await request.json()
        tok = engine.engine.tokenizer
        if body.get("messages"):
            msgs = [ChatMessage(**m) for m in body["messages"]]
            text = tok.apply_chat_template(msgs)
        else:
            text = body.get("prompt") or ""
        ids = tok.encode(text, add_special_tokens=body.get("add_special_tokens", True))
        return web.json_response(
            {"tokens": ids, "count": len(ids),
             "max_model_len": engine.engine.cfg.max_model_len}
        )

    async def detokenize(request: web.Request) -> web.Response:
        body = await request.json()
        text = engine.engine.tokenizer.decode(body.get("tokens", []))
        return web.json_response({"prompt": text})

    # -- admin / health ------------------------------------------------

    async def health(request: web.Request) -> web.Response:
        if engine.is_healthy():
            # Draining and warming are still healthy (liveness: the pod
            # must not be restarted mid-drain or mid-precompile) — the
            # status string tells K8s dashboards and humans apart from a
            # routable engine.
            status = (
                "draining" if engine.draining
                else "warming" if engine.warming
                else "ok"
            )
            return web.json_response({"status": status})
        return web.json_response(
            {"status": "unhealthy", "error": engine.step_error}, status=503
        )

    async def ready(request: web.Request) -> web.Response:
        """Readiness (the K8s readinessProbe target and router discovery's
        warming probe): 200 only once the startup precompile pass has
        finished and the engine accepts work. Distinct from /health —
        a warming engine is alive but must receive no traffic, or its
        first requests absorb XLA compiles."""
        warmup = dict(engine.engine.warmup_summary or {})
        warmup["mode"] = engine.engine.cfg.warmup
        if engine.warmup_error:
            warmup["error"] = engine.warmup_error
        if engine.ready:
            engine.engine.note_ready()
            return web.json_response({"ready": True, "warmup": warmup})
        # Reason mirrors AsyncLLMEngine.ready's conjuncts, in severity
        # order.
        reason = (
            "unhealthy" if not engine.is_healthy()
            else "warming" if engine.warming
            else "sleeping" if engine.sleeping
            else "draining"
        )
        return web.json_response(
            {"ready": False, "reason": reason, "warmup": warmup}, status=503
        )

    async def metrics_endpoint(request: web.Request) -> web.Response:
        stats = engine.engine.stats()
        metrics.refresh(stats)
        # KV occupancy / high watermark + preemption/swap counters for the
        # pst_engine_* surface refresh from the same stats snapshot.
        ENGINE_TELEMETRY.refresh_from_stats(stats)
        # pst_stage_duration_seconds lives in the shared observability
        # registry and pst_engine_* in the engine-telemetry registry
        # (docs/observability.md) — append both to the engine's own. A
        # scraper negotiating OpenMetrics gets the exemplar-carrying
        # exposition; plain scrapes stay byte-identical.
        body, content_type = render_registries(
            (metrics.registry, OBS_REGISTRY, ENGINE_TELEMETRY_REGISTRY),
            request.headers.get("Accept"),
        )
        if content_type == "text/plain":
            return web.Response(body=body, content_type="text/plain")
        return web.Response(
            body=body, headers={"Content-Type": content_type}
        )

    # On-demand profiling state: one capture at a time (jax.profiler is a
    # process-global singleton — a second start_trace would raise).
    profile_lock = asyncio.Lock()

    async def debug_profile(request: web.Request) -> web.Response:
        """Capture a ``jax.profiler`` trace for N ms into a directory
        (``--profile-dir``; TensorBoard-loadable). Guarded twice: the
        ``--profiling`` flag must be on, and when an API key is configured
        the endpoint requires it like the work endpoints. On CPU backends
        this is a graceful no-op — there is no device timeline worth the
        capture overhead. The response gives ``start_s`` and ``stop_s``:
        how long the profiler took to start and to stop and write."""
        if not profiling:
            return _error(
                "profiling is disabled (start the engine with --profiling)",
                403, "permission_error",
            )
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:  # noqa: BLE001 — empty/garbage body = defaults
                body = {}
        if not isinstance(body, dict):  # e.g. a bare JSON list
            body = {}
        try:
            duration_ms = float(
                body.get("duration_ms")
                or request.query.get("duration_ms", 1000)
            )
        except (TypeError, ValueError):
            return _error("duration_ms must be a number")
        duration_ms = min(max(duration_ms, 10.0), profile_max_ms)
        out_dir = str(body.get("dir") or profile_dir)

        import jax

        if jax.default_backend() == "cpu":
            return web.json_response({
                "status": "skipped",
                "reason": "no accelerator backend (cpu) — nothing to profile",
                "duration_ms": duration_ms,
            })
        if profile_lock.locked():
            return _error("a profile capture is already running", 409,
                          "conflict_error")
        async with profile_lock:
            os.makedirs(out_dir, exist_ok=True)
            # The pst.* spans of the step loop (obs/engine_telemetry
            # ``phase``) say what the Python tracer was there to say, and
            # that tracer slows the very host code whose gaps the trace is
            # read for: off. Starting and stopping a capture takes the
            # profiler seconds, so both run in the executor and the loop
            # keeps serving streams meanwhile.
            options = jax.profiler.ProfileOptions()
            for key, val in profile_options_attrs().items():
                setattr(options, key, val)
            loop = asyncio.get_running_loop()

            def start():
                # the step loop holds no cycle to its bar meanwhile
                with ENGINE_TELEMETRY.profiler_starting():
                    jax.profiler.start_trace(
                        out_dir, profiler_options=options)

            t0 = time.perf_counter()
            await loop.run_in_executor(None, start)
            start_s = time.perf_counter() - t0
            try:
                await asyncio.sleep(duration_ms / 1000.0)
            finally:
                t0 = time.perf_counter()
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                stop_s = time.perf_counter() - t0
        logger.info(
            "profile captured: %.0f ms -> %s (start %.2f s, stop %.2f s)",
            duration_ms, out_dir, start_s, stop_s,
        )
        return web.json_response({
            "status": "ok", "dir": out_dir, "duration_ms": duration_ms,
            "start_s": start_s, "stop_s": stop_s,
        })

    async def debug_requests(request: web.Request) -> web.Response:
        """Engine-side timeline ring buffer (same shape as the router's
        GET /debug/requests, shared handler): per-request spans for
        admission, queue wait, prefill, decode — joinable to the router's
        timelines by trace id."""
        return debug_requests_response(recorder, request)

    async def debug_state(request: web.Request) -> web.Response:
        """One-shot engine introspection (docs/observability.md "Fleet
        debugging"): the scheduler/KV stats snapshot the metrics surface
        derives from, plus compile totals — what /debug/fleet shows for
        this engine, straight from the source for cross-validation."""
        stats = engine.engine.stats()
        return web.json_response({
            "model": model_name,
            "ready": engine.ready,
            "draining": engine.draining,
            "warming": engine.warming,
            "sleeping": engine.sleeping,
            "in_flight": engine.num_inflight(),
            "compiles_total": ENGINE_TELEMETRY.compile_count(),
            "flight": engine.engine.flight.stats(),
            "stats": {
                k: v for k, v in stats.items()
                if isinstance(v, (int, float, str, bool))
            },
        })

    async def debug_flight(request: web.Request) -> web.Response:
        """Flight-recorder dump (docs/observability.md "Flight
        recorder"): the last-N per-step records (``?n=``) or a time
        window (``?window_s=``), plus the retained auto-snapshots
        (tail outliers, live compiles, fatal steps). Guarded like the
        work endpoints when an API key is configured — step records
        carry request ids and tenant mix."""
        flight = engine.engine.flight
        try:
            n = int(request.query["n"]) if "n" in request.query else None
            window_s = (
                float(request.query["window_s"])
                if "window_s" in request.query else None
            )
        except (TypeError, ValueError):
            return _error("n and window_s must be numbers")
        # ?snapshots=1: include snapshots a PREVIOUS process persisted to
        # --flight-snapshot-dir — the post-mortem collection path.
        include_restored = request.query.get("snapshots") in ("1", "true")
        return web.json_response(flight.to_payload(
            n=n, window_s=window_s, include_restored=include_restored,
        ))

    async def is_sleeping(request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": engine.sleeping})

    async def sleep(request: web.Request) -> web.Response:
        level = int(request.query.get("level", "1"))
        engine.sleep(level)
        return web.json_response({"status": "sleeping", "level": level})

    async def wake_up(request: web.Request) -> web.Response:
        engine.wake_up()
        return web.json_response({"status": "awake"})

    async def drain(request: web.Request) -> web.Response:
        """Graceful drain: stop admitting new sequences, finish in-flight
        ones. ``?wait=1`` blocks (up to ``?timeout=`` seconds, default 30)
        until the engine is idle — the preStop-hook shape."""
        engine.drain()
        if request.query.get("wait"):
            try:
                timeout = float(request.query.get("timeout", "30"))
            except ValueError:
                timeout = 30.0
            deadline = time.time() + timeout
            while time.time() < deadline and engine.num_inflight() > 0:
                await asyncio.sleep(0.1)
        return web.json_response(
            {"status": "draining", "in_flight": engine.num_inflight()}
        )

    async def undrain(request: web.Request) -> web.Response:
        engine.undrain()
        return web.json_response(
            {"status": "accepting", "in_flight": engine.num_inflight()}
        )

    async def is_draining(request: web.Request) -> web.Response:
        return web.json_response(
            {"is_draining": engine.draining, "in_flight": engine.num_inflight()}
        )

    async def load_lora(request: web.Request) -> web.Response:
        """Parse the PEFT checkpoint and install it into a device bank slot
        (reference loadAdapter, loraadapter_controller.go:582-611). The
        safetensors read + device write run off the event loop."""
        body = await request.json()
        name = body.get("lora_name")
        if not name:
            return _error("lora_name required")
        if engine.engine.lora_manager is None:
            return _error("LoRA not enabled (--enable-lora)", 400)
        path = body.get("lora_path")
        try:
            ad = await asyncio.get_running_loop().run_in_executor(
                None, engine.engine.load_lora, name, path
            )
        except FileNotFoundError as e:
            return _error(str(e), 404, "not_found_error")
        except (ValueError, RuntimeError) as e:
            return _error(str(e), 400)
        return web.json_response(
            {"status": "ok", "name": ad.name, "rank": ad.rank, "slot": ad.slot}
        )

    async def unload_lora(request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        if not name:
            return _error("lora_name required")
        removed = await asyncio.get_running_loop().run_in_executor(
            None, engine.engine.unload_lora, name
        )
        return web.json_response({"status": "ok", "removed": bool(removed)})

    async def version(request: web.Request) -> web.Response:
        # Beside the package version, the device path this engine resolved
        # at start-up (platform, device_kind, mesh, kernel choices).
        return web.json_response(
            {"version": __version__, "device": engine.engine.runner.device_info}
        )

    app.router.add_get("/v1/models", list_models)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_post("/rerank", rerank)
    app.router.add_post("/v1/rerank", rerank)
    app.router.add_post("/v2/rerank", rerank)
    app.router.add_post("/score", score)
    app.router.add_post("/v1/score", score)
    app.router.add_post("/tokenize", tokenize)
    app.router.add_post("/detokenize", detokenize)
    app.router.add_get("/health", health)
    app.router.add_get("/ready", ready)
    app.router.add_get("/metrics", metrics_endpoint)
    app.router.add_get("/debug/requests", debug_requests)
    app.router.add_get("/debug/state", debug_state)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_get("/is_sleeping", is_sleeping)
    app.router.add_post("/sleep", sleep)
    app.router.add_post("/wake_up", wake_up)
    app.router.add_post("/drain", drain)
    app.router.add_post("/undrain", undrain)
    app.router.add_get("/is_draining", is_draining)
    app.router.add_post("/v1/load_lora_adapter", load_lora)
    app.router.add_post("/v1/unload_lora_adapter", unload_lora)
    app.router.add_get("/version", version)
    return app


def parse_engine_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description="production-stack-tpu serving engine (vllm-serve analogue)"
    )
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--model", default="tiny-llama-debug")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--max-model-len", type=int, default=4096)
    p.add_argument("--block-size", type=int, default=32)
    p.add_argument("--num-kv-blocks", type=int, default=None)
    p.add_argument(
        "--gpu-memory-utilization", "--hbm-utilization",
        dest="hbm_utilization", type=float, default=0.9,
    )
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument(
        "--max-num-batched-tokens", dest="max_prefill_tokens", type=int, default=2048
    )
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1)
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument("--sequence-parallel-size", type=int, default=1,
                   help="ring-attention context parallel (encode path)")
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="MoE expert bank sharding over the ep mesh axis")
    p.add_argument("--moe-impl", default="auto",
                   choices=["auto", "ragged", "dense"])
    p.add_argument("--kv-cache-dtype", default=None)
    # Weight-only int8 (per-output-channel scales): the `vllm serve
    # --quantization` analogue; what fits an 8B model + KV on one 16 GiB v5e.
    p.add_argument("--quantization", default=None, choices=["int8", "int4"])
    p.add_argument("--attn-impl", default="auto", choices=["auto", "gather", "pallas"])
    p.add_argument("--enable-prefix-caching", action="store_true", default=True)
    p.add_argument(
        "--no-enable-prefix-caching", dest="enable_prefix_caching",
        action="store_false",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--exit-with-parent", action="store_true",
                   help="shut down when the process that started this server "
                        "is gone (a supervisor or harness that was killed "
                        "must not leave a server holding the chip)")
    p.add_argument("--api-key", default=None)
    p.add_argument("--sentry-dsn", default=None)
    # LoRA serving (vLLM --enable-lora analogue).
    p.add_argument("--enable-lora", action="store_true", default=False)
    p.add_argument("--max-loras", type=int, default=8)
    p.add_argument("--max-lora-rank", type=int, default=16)
    p.add_argument("--lora-dir", default="/adapters")
    # Decode burst + batch-shape floors.
    p.add_argument("--num-decode-steps", type=int, default=1)
    p.add_argument("--min-decode-bucket", type=int, default=1)
    # Overlapped decode pipeline (docs/engine.md "Overlapped decode
    # pipeline"): burst N+1 dispatches as soon as burst N's tokens are
    # fetched, N's host bookkeeping overlaps N+1's execution; engages
    # whenever the decode batch can be chained, and an arrival waits for
    # the one burst in flight.
    p.add_argument("--overlap-decode", dest="overlap_decode",
                   action="store_true", default=True)
    p.add_argument("--no-overlap-decode", dest="overlap_decode",
                   action="store_false",
                   help="disable the overlapped decode pipeline "
                        "(synchronous hot loop)")
    # Speculative decoding (n-gram prompt lookup; 0 = off).
    p.add_argument("--speculative-ngram", type=int, default=0,
                   help="max draft tokens per step via n-gram prompt lookup")
    p.add_argument("--speculative-mtp", type=int, default=0,
                   help="1: verify one draft a row from the model's own "
                        "multi-token-prediction module every decode step and "
                        "make the next, on the device (a model with the "
                        "module only; 0 = off)")
    p.add_argument("--ngram-min", type=int, default=1)
    p.add_argument("--ngram-max", type=int, default=3)
    p.add_argument("--ngram-lookback", type=int, default=8192,
                   help="cap prompt-lookup scan to last N tokens (0 = all)")
    # Live-sequence KV swap (vLLM --swap-space analogue; engine/swap.py).
    p.add_argument("--kv-swap", action="store_true", default=True)
    p.add_argument("--no-kv-swap", dest="kv_swap", action="store_false")
    p.add_argument("--swap-quantum-tokens", type=int, default=256,
                   help="decode tokens before a running seq may rotate out "
                        "for parked/queued work (0 = only under pressure)")
    p.add_argument("--swap-stash-blocks", type=int, default=4096,
                   help="host-DRAM budget for stashed tail pages (KV pages)")
    # KV tiering / controller (LMCache env-var analogues).
    p.add_argument("--cpu-offload-blocks", type=int, default=0)
    p.add_argument("--remote-kv-url", default=None,
                   help="kvserver base URL; a comma-separated list makes "
                        "the engine a sharded-ring client "
                        "(docs/kvserver.md)")
    p.add_argument("--kv-replication", type=int, default=2,
                   help="replicas per KV block/manifest on the kvserver "
                        "ring (clamped to the shard count)")
    p.add_argument("--cache-controller-url", default=None)
    p.add_argument("--engine-url", default=None)
    p.add_argument(
        "--kv-role", default="none",
        choices=["none", "producer", "consumer", "both"],
    )
    # Streamed disagg KV handoff (docs/disagg.md): consumer prefetch
    # batching depth and the wall the decode engine waits for a prefill's
    # manifest completion before degrading to the fused path.
    p.add_argument("--kv-prefetch-depth", type=int, default=64,
                   help="max KV pages per batched GET while following a "
                        "disagg prefill's manifest")
    p.add_argument("--kv-transfer-timeout-s", type=float, default=10.0,
                   help="seconds the decode engine waits for a disagg "
                        "manifest's completion marker before recomputing "
                        "the prefill locally (fused fallback)")
    # Cross-encoder scoring sidecar for /rerank and /score (bge-reranker-
    # style HF dir or a bert preset). Without it those endpoints fall back
    # to embedding cosine similarity.
    p.add_argument("--scoring-model", default=None)
    # Deadline shedding (docs/resilience.md "Deadlines & hedging"): honor
    # the router-propagated X-PST-Deadline-Ms budget.
    p.add_argument("--deadline-shedding", dest="deadline_shedding",
                   action="store_true", default=True)
    p.add_argument("--no-deadline-shedding", dest="deadline_shedding",
                   action="store_false")
    # Tenant-aware scheduling (docs/multi-tenancy.md): honor the
    # router-stamped X-PST-Tenant / X-PST-Tenant-Class headers in the
    # ready queue (weighted-fair admission, batch preempted first).
    p.add_argument("--tenant-fairness", dest="tenant_fairness",
                   action="store_true", default=True)
    p.add_argument("--no-tenant-fairness", dest="tenant_fairness",
                   action="store_false")
    # Request tracing (docs/observability.md): engine-side spans for
    # admission / queue wait / prefill / decode, joined to the router's
    # trace via the propagated traceparent.
    p.add_argument("--tracing", dest="tracing", action="store_true",
                   default=True)
    p.add_argument("--no-tracing", dest="tracing", action="store_false")
    p.add_argument("--debug-requests-buffer", type=int, default=256,
                   help="completed request timelines kept for "
                        "GET /debug/requests (0 disables the endpoint)")
    p.add_argument("--log-format", choices=["text", "json"], default="text",
                   help="log output format: 'json' emits one JSON object "
                        "per line enriched with trace_id/request_id/"
                        "tenant/engine_id (docs/observability.md "
                        "\"Structured logging\")")
    # On-demand jax.profiler capture (docs/observability.md "Profiling").
    p.add_argument("--profiling", dest="profiling", action="store_true",
                   default=False,
                   help="enable POST /debug/profile (on-demand jax.profiler "
                        "trace capture; no-op on CPU backends)")
    p.add_argument("--profile-dir", default="/tmp/pst_profiles",
                   help="directory POST /debug/profile writes traces to")
    p.add_argument("--profile-max-ms", type=float, default=60_000.0,
                   help="the longest capture POST /debug/profile takes, "
                        "whatever duration_ms asks for (the answer gives "
                        "the length taken). The answer comes once the "
                        "capture is converted and written, which takes a "
                        "process's first capture about 130 us a device "
                        "operation's event: 130 s for 3 s of a decode step "
                        "of 4,700 operations at 60 steps a second "
                        "(docs/observability.md \"Profiling\")")
    p.add_argument("--startup-phases", dest="startup_phases",
                   action="store_true", default=True)
    p.add_argument("--no-startup-phases", dest="startup_phases",
                   action="store_false",
                   help="do not export pst_engine_startup_seconds")
    # Ahead-of-time precompilation + persistent compile cache
    # (docs/engine.md "Warmup & precompilation"). The helm chart deploys
    # with --warmup full; bare CLI runs default to off so dev loops and
    # embedded use stay instant.
    p.add_argument("--warmup", default="off",
                   choices=["off", "lazy", "full"],
                   help="shape-bucket precompilation before /ready flips: "
                        "full = entire lattice, lazy = the core set the "
                        "first requests hit, off = compile on demand")
    p.add_argument("--warmup-bucket-budget", type=int, default=0,
                   help="cap warmup to this many lattice buckets, "
                        "most-likely-first (0 = whole lattice)")
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent JAX compilation cache root; compiled "
                        "executables land in a subdirectory keyed on "
                        "model+mesh+dtype+code version so warm restarts "
                        "skip XLA entirely")
    # Flight recorder + cost attribution (docs/observability.md "Flight
    # recorder" / "Cost attribution").
    p.add_argument("--flight-buffer", type=int, default=512,
                   help="per-step flight-recorder ring capacity (GET "
                        "/debug/flight; auto-snapshots on tail outliers "
                        "and SIGTERM/fatal; 0 disables recording)")
    p.add_argument("--flight-snapshot-dir", default=None,
                   help="persist retained flight snapshots as JSON files "
                        "under this directory (bounded, oldest-first "
                        "eviction) and load them back into GET "
                        "/debug/flight?snapshots=1 after a restart — "
                        "tail-outlier post-mortems survive process death")
    p.add_argument("--cost-attribution", dest="cost_attribution",
                   action="store_true", default=True)
    p.add_argument("--no-cost-attribution", dest="cost_attribution",
                   action="store_false",
                   help="disable per-request device-seconds attribution "
                        "(X-PST-Cost header, pst_request_device_seconds, "
                        "pst_tenant_device_seconds)")
    return p.parse_args(argv)


def engine_config_from_args(args: argparse.Namespace) -> EngineConfig:
    return EngineConfig(
        model=args.model,
        tokenizer=args.tokenizer,
        served_model_name=args.served_model_name,
        max_model_len=args.max_model_len,
        block_size=args.block_size,
        num_kv_blocks=args.num_kv_blocks,
        hbm_utilization=args.hbm_utilization,
        max_num_seqs=args.max_num_seqs,
        max_prefill_tokens=args.max_prefill_tokens,
        tensor_parallel_size=args.tensor_parallel_size,
        pipeline_parallel_size=args.pipeline_parallel_size,
        data_parallel_size=args.data_parallel_size,
        sequence_parallel_size=args.sequence_parallel_size,
        expert_parallel_size=args.expert_parallel_size,
        kv_cache_dtype=args.kv_cache_dtype,
        quantization=args.quantization,
        attn_impl=args.attn_impl,
        moe_impl=args.moe_impl,
        enable_prefix_caching=args.enable_prefix_caching,
        seed=args.seed,
        enable_lora=args.enable_lora,
        max_loras=args.max_loras,
        max_lora_rank=args.max_lora_rank,
        lora_dir=args.lora_dir,
        num_decode_steps=args.num_decode_steps,
        overlap_decode=args.overlap_decode,
        min_decode_bucket=args.min_decode_bucket,
        speculative_ngram=args.speculative_ngram,
        speculative_mtp=args.speculative_mtp,
        ngram_min=args.ngram_min,
        ngram_max=args.ngram_max,
        ngram_lookback=args.ngram_lookback,
        kv_swap=args.kv_swap,
        swap_quantum_tokens=args.swap_quantum_tokens,
        swap_stash_blocks=args.swap_stash_blocks,
        cpu_offload_blocks=args.cpu_offload_blocks,
        remote_kv_url=args.remote_kv_url,
        kv_replication=args.kv_replication,
        cache_controller_url=args.cache_controller_url,
        engine_url=args.engine_url,
        kv_role=args.kv_role,
        kv_prefetch_depth=args.kv_prefetch_depth,
        kv_transfer_timeout_s=args.kv_transfer_timeout_s,
        deadline_shedding=args.deadline_shedding,
        tenant_fairness=args.tenant_fairness,
        warmup=args.warmup,
        warmup_bucket_budget=args.warmup_bucket_budget,
        compile_cache_dir=args.compile_cache_dir,
        flight_buffer=args.flight_buffer,
        flight_snapshot_dir=args.flight_snapshot_dir,
        cost_attribution=args.cost_attribution,
    )


async def controller_report_loop(
    engine: AsyncLLMEngine, controller_url: str, engine_url: str, interval: float
) -> None:
    """Snapshot-register resident chunk hashes with the cache controller
    (LMCACHE controller heartbeat analogue; feeds KV-aware routing)."""
    import aiohttp

    model = engine.engine.model_name
    while True:
        try:
            eng = engine.engine
            cutoff = time.time() - eng.CHUNK_CLAIM_TTL
            hashes = [
                h for h, t in list(eng.resident_chunk_hashes.items()) if t >= cutoff
            ]
            async with aiohttp.ClientSession() as sess:
                await sess.post(
                    f"{controller_url.rstrip('/')}/register",
                    json={
                        "url": engine_url,
                        "model": model,
                        "hashes": hashes,
                        "replace": True,
                    },
                    timeout=aiohttp.ClientTimeout(total=5),
                )
        except Exception as e:  # noqa: BLE001 — registration is best-effort
            logger.debug("controller registration failed: %s", e)
        await asyncio.sleep(interval)


def profile_options_attrs() -> dict:
    """What ``POST /debug/profile`` sets on ``jax.profiler.ProfileOptions``
    (``scripts/tpu_profile_stop_probe.py`` times a capture under them)."""
    return {"python_tracer_level": 0, "host_tracer_level": 2}


def exit_with_parent(poll_s: float = 1.0, grace_s: float = 20.0) -> None:
    """``--exit-with-parent``: watch the process that started this one and,
    once it is gone (this process was handed to another parent), take the
    ordinary way out: SIGTERM to itself, which before the app runs ends the
    process and afterwards is aiohttp's graceful shutdown; ``grace_s`` later
    whatever is left is ended."""
    parent = os.getppid()

    def watch() -> None:
        while os.getppid() == parent:
            # pstlint: disable=async-blocking(the poll of the watcher's own daemon thread, started before the app exists; never on the event loop)
            time.sleep(poll_s)
        logger.warning("parent process %d is gone: shutting down", parent)
        os.kill(os.getpid(), signal.SIGTERM)
        # pstlint: disable=async-blocking(the same daemon thread, waiting out the graceful shutdown it asked for)
        time.sleep(grace_s)
        os._exit(1)

    threading.Thread(target=watch, name="parent-watch", daemon=True).start()


def main(argv=None) -> None:
    args = parse_engine_args(argv)
    if args.exit_with_parent:
        exit_with_parent()
    configure_logging(
        getattr(args, "log_format", "text") or "text",
        component="engine",
        engine_id=f"{args.host}:{args.port}",
    )
    cfg = engine_config_from_args(args)
    # Must be set before the engine constructs: the runner records the
    # load/shard phases during __init__.
    ENGINE_TELEMETRY.startup_enabled = args.startup_phases

    # Optional error reporting + tracing (no-ops without the SDKs; OTel
    # activates via the standard OTEL_* env contract the chart wires in).
    from ..utils_tracing import init_otel, init_sentry

    init_sentry(args.sentry_dsn)
    init_otel("pst-engine")

    # Multi-host boot (the ray-cluster head/worker analogue): every process
    # joins the jax.distributed runtime; host 0 serves HTTP, the rest mirror
    # device steps (SURVEY.md §7 hard part 3 — single-program serving).
    from ..parallel.distributed import is_primary, maybe_init_distributed

    multihost = maybe_init_distributed()
    if multihost and not is_primary():
        from .multihost import make_follower_runner, run_follower

        run_follower(make_follower_runner(cfg))
        return

    engine = AsyncLLMEngine(cfg)
    if multihost:
        from .multihost import StepPublisher

        engine.engine.runner.publisher = StepPublisher()
    cross_encoder = None
    if args.scoring_model:
        from .cross_encoder import CrossEncoder

        cross_encoder = CrossEncoder(args.scoring_model)
        logger.info(
            "cross-encoder scoring model loaded: %s", cross_encoder.cfg.name
        )
    app = create_engine_app(
        engine, api_key=args.api_key, cross_encoder=cross_encoder,
        tracing=args.tracing,
        debug_requests_buffer=args.debug_requests_buffer,
        profiling=args.profiling,
        profile_dir=args.profile_dir,
        profile_max_ms=args.profile_max_ms,
    )

    async def on_startup(app):
        engine.start(asyncio.get_event_loop())
        if cfg.cache_controller_url:
            engine_url = cfg.engine_url or f"http://{args.host}:{args.port}"
            app["controller_task"] = spawn_owned(
                controller_report_loop(
                    engine, cfg.cache_controller_url, engine_url, 10.0
                ),
                name="engine-controller-report",
            )

    async def on_cleanup(app):
        task = app.get("controller_task")
        if task:
            task.cancel()
        # SIGTERM lands here via aiohttp's graceful shutdown: freeze the
        # flight ring so the terminating pod leaves a post-mortem in its
        # logs (the /debug/flight endpoint dies with the process).
        try:
            snap = engine.engine.flight.snapshot("sigterm")
            if snap["records"]:
                logger.info(
                    "flight snapshot (sigterm): %d steps recorded, tail=%s",
                    snap["total_steps"], snap["records"][-3:],
                )
        except Exception:  # noqa: BLE001 — shutdown must proceed
            pass
        publisher = engine.engine.runner.publisher
        if publisher is not None:
            publisher.shutdown()  # release follower loops before exiting
        engine.shutdown()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    web.run_app(app, host=args.host, port=args.port, access_log=None)


if __name__ == "__main__":
    main()
