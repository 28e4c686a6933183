"""LLMEngine: the synchronous serving core (add_request / step / outputs).

Equivalent role to the vLLM engine the reference stack drives over HTTP
(SURVEY.md §1 "Serving engine" row). One `step()` = one scheduler decision +
one (or a few) jitted device steps + host-side bookkeeping: detokenization,
stop handling, prefix-block commitment, and the counters the `/metrics`
endpoint exports under the `vllm:`-compatible names the router's stats
scraper parses (`stats/engine_stats.py:42-85` contract).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence as Seq, Union

import numpy as np
import psutil

from ..kvcache.hashing import CHUNK_TOKENS
from ..logging_utils import init_logger
from ..models.registry import get_model_config
from ..obs.engine_telemetry import ENGINE_TELEMETRY
from ..obs.flight import NULL_FLIGHT_RECORDER, FlightRecorder
from .config import EngineConfig
from .kv_manager import BlockAllocator
from .runner import ModelRunner
from .scheduler import Scheduler, SchedulerConfig
from .sequence import SamplingParams, Sequence
from .tokenizer import get_tokenizer

logger = init_logger(__name__)

# Why a decode chain was drained (`LLMEngine._chain_break_reason`, and
# `abort_all_requests`): the ``reason`` label of ``pst:pipeline_breaks_total``.
# A prefill alone drains none: the rows it completes join the chain, unless
# there is no row for them (``row_bucket``), they need another program than
# the chain's (``sampling_variant``) or one of the older reasons holds;
# ``prefill`` is what is left, a preempted sequence's recompute.
CHAIN_BREAK_REASONS = (
    "prefill", "blocked_on_locked", "decode_set", "depth", "table_width",
    "queue", "not_eligible", "abort_all", "row_bucket", "sampling_variant",
)


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    text_delta: str = ""
    new_token_ids: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    num_cached_prompt_tokens: int = 0
    ttft: Optional[float] = None
    # TTFT decomposition (monotonic durations, seconds): time queued before
    # the first scheduler admission, first admission → first token, and —
    # on the finished output — first token → completion. The server turns
    # these into engine_queue/prefill/decode spans + stage histograms.
    queue_time: Optional[float] = None
    prefill_time: Optional[float] = None
    decode_time: Optional[float] = None
    # The stamp prefill_time ends at (time.monotonic() on the step thread):
    # the server measures the first token's way to the socket from it.
    first_token_time: Optional[float] = None
    # One entry per new token when SamplingParams.logprobs is set:
    # {"token_id", "logprob", "top": [(token_id, logprob), ...]}.
    logprobs: Optional[List[dict]] = None
    # XLA compiles the step that produced this output absorbed
    # ({"kind", "shape_bucket", "seconds"}): the HTTP layer attaches them
    # as `compile` span events so a recompile shows up inside the victim
    # request's timeline (docs/observability.md "Engine telemetry").
    compile_events: Optional[List[dict]] = None
    # Per-request cost attribution (finished outputs only, when
    # cost_attribution is on): prefill/decode device-seconds, KV
    # page-seconds, queue wait — the X-PST-Cost header / usage extension
    # payload (docs/observability.md "Cost attribution").
    cost: Optional[dict] = None


class LLMEngine:
    def __init__(self, cfg: EngineConfig, mesh=None):
        t_init = time.perf_counter()
        # Startup decomposition, phase 0: process start to this line, which
        # is the interpreter and the imports (jax, the server's own).
        ENGINE_TELEMETRY.record_startup_phase(
            "imports", time.time() - psutil.Process().create_time()
        )
        self.cfg = cfg
        self.model_cfg = get_model_config(cfg.model)
        # Before the runner wires any jit: executables compiled earlier
        # are never written back to the persistent cache.
        from .precompile import configure_compile_cache

        compile_cache_path = configure_compile_cache(cfg, self.model_cfg)
        tok_spec = cfg.tokenizer or (cfg.model if os.path.isdir(cfg.model) else None)
        t_tok = time.perf_counter()
        self.tokenizer = get_tokenizer(tok_spec, self.model_cfg.vocab_size)
        t_runner = time.perf_counter()
        ENGINE_TELEMETRY.record_startup_phase("tokenizer", t_runner - t_tok)
        logger.info(
            "tokenizer: loader %s, path %s, %.2f s",
            self.tokenizer.loader, tok_spec, t_runner - t_tok,
        )
        self.runner = ModelRunner(cfg, self.model_cfg, mesh)
        t_runner_s = time.perf_counter() - t_runner
        self.runner.device_info["compile_cache_dir"] = compile_cache_path
        self.runner.device_info["tokenizer_loader"] = self.tokenizer.loader
        # Step programs are kept beside the executables (a `programs/`
        # directory there): a shape this tree built before is loaded at
        # its first use, not traced again.
        self.runner.place_program_store(compile_cache_path)
        if cfg.cpu_offload_blocks > 0 or cfg.remote_kv_url:
            from .cache_tiering import TieredAllocator, create_remote_client

            host_blocks = cfg.cpu_offload_blocks
            if (
                host_blocks == 0
                and cfg.remote_kv_url
                and cfg.kv_role in ("consumer", "both")
            ):
                # The consumer-side prefetch stages published pages in the
                # host pool so admission's match_prefix faults them up —
                # a consumer engine without an explicit offload budget
                # still needs a staging tier (docs/disagg.md).
                host_blocks = max(self.runner.num_blocks // 2, 1024)
            self.allocator: BlockAllocator = TieredAllocator(
                self.runner.num_blocks,
                cfg.block_size,
                page_io=self.runner,
                host_blocks=host_blocks,
                remote=create_remote_client(
                    cfg.remote_kv_url, replication=cfg.kv_replication
                )
                if cfg.remote_kv_url
                else None,
                enable_prefix_caching=cfg.enable_prefix_caching,
            )
        else:
            self.allocator = BlockAllocator(
                self.runner.num_blocks, cfg.block_size, cfg.enable_prefix_caching,
                **self._pool_groups(),
            )
        # Streamed disagg KV handoff (docs/disagg.md): a producer engine
        # ships each prefill chunk's committed pages under the request's
        # kv_transfer id as the chunk completes (worker thread, batched
        # puts + manifest appends); a consumer engine follows manifests
        # and stages published pages in the host pool while the remote
        # prefill is still running.
        self.kv_publisher = None
        self.kv_prefetcher = None
        remote = getattr(self.allocator, "remote", None)
        if remote is not None and cfg.kv_role in ("producer", "both"):
            from .kv_handoff import KVHandoffPublisher

            self.kv_publisher = KVHandoffPublisher(remote)
        if (
            remote is not None
            and cfg.kv_role in ("consumer", "both")
            and getattr(self.allocator, "host_pool", None) is not None
        ):
            from .kv_handoff import KVHandoffPrefetcher

            self.kv_prefetcher = KVHandoffPrefetcher(
                remote,
                self.allocator.host_pool,
                timeout_s=cfg.kv_transfer_timeout_s,
                depth=cfg.kv_prefetch_depth,
            )
        if cfg.kv_swap:
            from .swap import KVSwapper

            self.swapper: Optional["KVSwapper"] = KVSwapper(
                self.runner, max_stash_blocks=cfg.swap_stash_blocks
            )
        else:
            self.swapper = None
        self.scheduler = Scheduler(
            SchedulerConfig(
                max_num_seqs=cfg.max_num_seqs,
                max_prefill_tokens=cfg.max_prefill_tokens,
                max_model_len=cfg.max_model_len,
                num_decode_steps=cfg.num_decode_steps,
                # The in-flight continuation writes one burst past the host
                # view, so its pages must already exist at dispatch time
                # (a chain can start on any pass).
                # Spec engines never pipeline (_pipeline_ok defers to
                # speculation), so they keep the tighter reservation.
                decode_lookahead=(
                    2 if cfg.overlap_decode and not cfg.speculative_ngram
                    else 1
                ),
                # (a verify-and-draft step writes its draft's position and,
                # one slot ahead, the draft layer's entry for it; a chained
                # one starts up to two tokens past the host's view)
                spec_tokens=cfg.speculative_ngram or cfg.speculative_mtp * (
                    4 if cfg.overlap_decode else 2),
                mtp=bool(cfg.speculative_mtp),
                swap_quantum=cfg.swap_quantum_tokens,
                deadline_shedding=cfg.deadline_shedding,
                tenant_fairness=cfg.tenant_fairness,
            ),
            self.allocator,
            swapper=self.swapper,
        )
        # Speculative-decoding counters (engine.stats / observability).
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        # Pipelined-decode bookkeeping: membership of the in-flight burst
        # (a row each, including members that finished meanwhile, until a
        # new member takes the row) and sequences whose page release is
        # deferred, each with the count of chained bursts that had been
        # dispatched when it left: the last that can write through its
        # pages. ``_bursts_fetched`` of them have been fetched.
        self._burst_seqs: List[Sequence] = []
        self._burst_n = 0
        self._burst_deferred: List[tuple] = []
        self._bursts_fetched = 0
        if cfg.enable_lora:
            from .lora import LoraManager

            self.lora_manager: Optional["LoraManager"] = LoraManager(
                self.model_cfg, cfg.max_loras, cfg.max_lora_rank, cfg.lora_dir
            )
        else:
            self.lora_manager = None
        # Unloaded-adapter slots awaiting their last in-flight sequence.
        self._retiring_slots: set = set()
        # What the decode loop did: every decode dispatch, those of them
        # that were chained (a chain's start and its continuations), and
        # each drained chain by the reason it could not go on.
        self.decode_dispatches_total = 0
        self.pipelined_bursts_total = 0
        # Layers a decode dispatch ran, by the host's count: the layer
        # stack's passes (1 but for a looped stack) x its layers x the
        # burst's depth (a later per-token exit would run fewer).
        self.decode_layer_passes_total = 0
        self.pipeline_breaks = {why: 0 for why in CHAIN_BREAK_REASONS}
        # Prefill steps a chain went on behind, with no drain.
        self.chain_kept_prefills_total = 0
        # Flight recorder (docs/observability.md "Flight recorder"):
        # always-on bounded ring of per-step records, fed through
        # ENGINE_TELEMETRY's dispatch path; this engine's scheduler/KV
        # state rides each record via the probe closure. Attached last-
        # wins: a fresh engine in one process must own the sink.
        self.flight = (
            FlightRecorder(
                cfg.flight_buffer, snapshot_dir=cfg.flight_snapshot_dir
            )
            if cfg.flight_buffer > 0 else NULL_FLIGHT_RECORDER
        )
        if self.flight.enabled:
            # Only a live ring takes the probe: installing a bound method
            # on the shared null singleton would pin this whole engine
            # (params + KV) past its lifetime.
            self.flight.set_probe(self._flight_probe)
        ENGINE_TELEMETRY.attach_flight(self.flight)
        # Compile events awaiting an output-emitting step (see step()).
        self._pending_compile_events: List[dict] = []
        # Precompile summary (engine/precompile.py): populated by
        # precompile(); the server's /ready payload surfaces it.
        self.warmup_summary: Optional[dict] = None
        self._seqs: Dict[str, Sequence] = {}
        # Incremental detokenizer state per request:
        # emitted text + [prefix_offset, read_offset) decode window.
        self._detok: Dict[str, Dict[str, object]] = {}
        # Chunk hashes resident in this engine's tiers (controller
        # registration: hash -> last-commit time).
        self.resident_chunk_hashes: Dict[int, float] = {}
        # Cumulative counters for /metrics.
        self.kv_published_blocks_total = 0
        self.num_preempted_total = 0
        self.prompt_tokens_total = 0
        self.generation_tokens_total = 0
        # Startup decomposition, phase 3: everything around the runner and
        # the tokenizer — compile cache, allocator, swapper, scheduler, LoRA
        # manager (pst_engine_startup_seconds{phase="warmup"}; the runner
        # records load and shard itself).
        self._constructed = time.perf_counter()
        ENGINE_TELEMETRY.record_startup_phase(
            "warmup",
            self._constructed - t_init - t_runner_s - (t_runner - t_tok),
        )
        self._precompile_s = 0.0
        self._serving = False

    @property
    def model_name(self) -> str:
        return self.cfg.served_model_name or self.model_cfg.name

    def _flight_probe(self) -> dict:
        """Scheduler/KV state attached to each flight record. Runs on the
        step thread (the thread that mutates the scheduler), right after
        a dispatch — plain reads, O(running)."""
        waiting, running, swapped, batch = self.scheduler.flight_depths()
        return {
            "waiting": waiting,
            "running": running,
            "swapped": swapped,
            "batch_tier_rows": batch,
            "kv_occupancy": self.allocator.usage,
            "preemptions": self.num_preempted_total,
        }

    def _finalize_cost(self, seq: Sequence) -> Optional[dict]:
        """Close a request's cost account exactly once: integrate the KV
        tail, export the per-phase histograms + tenant chip-time meter,
        and return the X-PST-Cost payload."""
        if not self.cfg.cost_attribution:
            return None
        if getattr(seq, "_cost_finalized", False):
            return getattr(seq, "_cost_final", None)
        now = time.monotonic()
        # BEFORE the scheduler releases block_ids: the tail residency
        # since the last charge point still belongs to this request.
        seq.charge_kv_pages(now)
        cost = seq.cost_snapshot(now)
        seq._cost_finalized = True
        seq._cost_final = cost
        ENGINE_TELEMETRY.record_request_cost(
            seq.tenant, seq.cost_prefill_s, seq.cost_decode_s
        )
        return cost

    # ------------------------------------------------------------------
    # Warmup precompilation (docs/engine.md "Warmup & precompilation")
    # ------------------------------------------------------------------

    def precompile(
        self, mode: Optional[str] = None, bucket_budget: Optional[int] = None
    ) -> dict:
        """Compile the padded shape-bucket lattice ahead of traffic.

        Runs on whatever thread calls it (the async engine's step thread,
        so HTTP probes stay responsive); records
        ``pst_engine_startup_seconds{phase="precompile"}`` and the
        coverage gauge, and returns the summary the server's ``/ready``
        payload exposes."""
        from .precompile import Precompiler

        t0 = time.perf_counter()
        summary = Precompiler(
            self.runner, self.cfg, mode=mode, bucket_budget=bucket_budget
        ).run()
        self._precompile_s = time.perf_counter() - t0
        ENGINE_TELEMETRY.record_startup_phase("precompile", self._precompile_s)
        self.warmup_summary = summary
        return summary

    def note_ready(self) -> None:
        """``/ready`` answered 200: the first time, close the startup
        decomposition with ``phase="serve"``, constructor done to here less
        the precompile pass (the HTTP server coming up, the step thread
        starting, the prober's interval). The phases then sum to process
        start to first ready."""
        if not self._serving:
            self._serving = True
            ENGINE_TELEMETRY.record_startup_phase(
                "serve",
                time.perf_counter() - self._constructed - self._precompile_s,
            )

    # ------------------------------------------------------------------
    # Requests
    # ------------------------------------------------------------------

    def add_request(
        self,
        request_id: str,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[Seq[int]] = None,
        sampling: Optional[SamplingParams] = None,
        arrival_time: Optional[float] = None,
        lora_name: Optional[str] = None,
        deadline: Optional[float] = None,
        tenant: Optional[str] = None,
        tenant_class: Optional[str] = None,
        kv_transfer: Optional[dict] = None,
    ) -> Sequence:
        if prompt_token_ids is None:
            prompt_token_ids = self.tokenizer.encode(prompt or "")
        if not prompt_token_ids:
            prompt_token_ids = [0]
        lora_idx, lora_scale, salt = 0, 0.0, 0
        if lora_name:
            if self.lora_manager is None:
                raise ValueError("LoRA not enabled on this engine")
            ad = self.lora_manager.get(lora_name)
            if ad is None:
                raise ValueError(f"LoRA adapter {lora_name!r} not loaded")
            lora_idx, lora_scale = ad.slot, ad.scaling
            # KV under an adapter differs from base KV: salt the prefix
            # hash chain so cache hits never cross adapters.
            import xxhash

            salt = xxhash.xxh64(lora_name.encode()).intdigest() & 0x7FFF_FFFF_FFFF_FFFF
        seq = Sequence(
            request_id,
            prompt_token_ids,
            sampling or SamplingParams(),
            arrival_time=arrival_time,
            lora_idx=lora_idx,
            lora_scale=lora_scale,
            cache_salt=salt,
            deadline=deadline if self.cfg.deadline_shedding else None,
            tenant=tenant or "default",
            tenant_class=tenant_class or "interactive",
            kv_transfer=kv_transfer,
        )
        self.scheduler.add(seq)
        self._seqs[request_id] = seq
        self._detok[request_id] = {"emitted": "", "prefix": 0, "read": 0}
        self.prompt_tokens_total += len(prompt_token_ids)
        return seq

    def load_lora(self, name: str, path: Optional[str] = None):
        """Load a PEFT adapter into a device bank slot (operator flow:
        POST /v1/load_lora_adapter → here)."""
        if self.lora_manager is None:
            raise ValueError("LoRA not enabled on this engine (--enable-lora)")
        ad, arrays = self.lora_manager.load(name, path)
        if arrays is not None:  # freshly parsed (not already resident)
            self.runner.install_adapter(ad.slot, arrays)
        return ad

    def unload_lora(self, name: str) -> bool:
        """Unregister the adapter. New requests for it fail immediately;
        in-flight sequences finish under its weights — the device slot is
        zeroed and recycled only after the last one drains (step() sweeps
        ``_retiring_slots``). Matches the reference engines' drain-then-free
        semantics for /v1/unload_lora_adapter."""
        if self.lora_manager is None:
            return False
        ad = self.lora_manager.unload(name)
        if ad is None:
            return False
        self._retiring_slots.add(ad.slot)
        self._sweep_retiring_slots()
        return True

    def _sweep_retiring_slots(self) -> None:
        if not self._retiring_slots:
            return
        live = {s.lora_idx for s in self._seqs.values() if s.lora_idx}
        for slot in [s for s in self._retiring_slots if s not in live]:
            self._retiring_slots.discard(slot)
            self.runner.uninstall_adapter(slot)
            self.lora_manager.release_slot(slot)

    def abort_request(self, request_id: str) -> bool:
        # Bill the device time an aborted request already consumed (the
        # tenant chip-time meter must not have a free-abort loophole),
        # while its pages are still owned.
        live = self._seqs.get(request_id)
        if live is not None:
            self._finalize_cost(live)
        if self.runner.burst_in_flight and any(
            s.request_id == request_id for s in self._burst_seqs
        ):
            seq = self.scheduler.detach(request_id)
            if seq is not None:
                self._defer_release(seq)
        else:
            seq = self.scheduler.abort(request_id)
        self._seqs.pop(request_id, None)
        self._detok.pop(request_id, None)
        return seq is not None

    def has_work(self) -> bool:
        # An in-flight burst counts as work even with empty queues: its
        # results must be drained (and its deferred pages released).
        return self.scheduler.has_work() or self.runner.burst_in_flight

    def abort_all_requests(self) -> int:
        """Abort everything queued or running (sleep / fatal-error paths)."""
        if self.runner.burst_in_flight:
            self.runner.burst_drain()  # discard: everything is going away
            self.pipeline_breaks["abort_all"] += 1
            self._burst_seqs = []
            self._burst_n = 0
            self._burst_fetched()
        rids = list(self._seqs.keys())
        for rid in rids:
            self.abort_request(rid)
        return len(rids)

    def clear_kv_state(self) -> None:
        """Invalidate all HBM-resident KV bookkeeping. Must accompany any
        operation that discards cache contents (sleep level 2): otherwise the
        hash→page maps would serve zero-filled pages as prefix hits. Lower
        tiers (host pool / remote) keep their pages — their copies were
        written before the drop and stay valid, LMCache-style."""
        self.abort_all_requests()
        host_pool = getattr(self.allocator, "host_pool", None)
        remote = getattr(self.allocator, "remote", None)
        if host_pool is not None or remote is not None:
            from .cache_tiering import TieredAllocator

            old_shutdown = getattr(self.allocator, "shutdown", None)
            if old_shutdown is not None:
                old_shutdown()  # stop the old kv-remote-push worker thread
            new = TieredAllocator(
                self.runner.num_blocks,
                self.cfg.block_size,
                page_io=self.runner,
                host_blocks=0,
                remote=remote,
                enable_prefix_caching=self.cfg.enable_prefix_caching,
            )
            new.host_pool = host_pool  # preserve the warm host tier
            self.allocator = new
        else:
            self.allocator = BlockAllocator(
                self.runner.num_blocks,
                self.cfg.block_size,
                self.cfg.enable_prefix_caching,
                **self._pool_groups(),
            )
        self.scheduler.allocator = self.allocator
        self.resident_chunk_hashes.clear()

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------

    def step(self) -> List[RequestOutput]:
        # The whole step is one phase of the loop (obs/engine_telemetry
        # ``phase``); schedule, batch_build, launch, wait and postprocess
        # are opened inside it, here and in the runner.
        with ENGINE_TELEMETRY.phase("step"):
            outputs = self._step_impl()
            if self._retiring_slots:
                with ENGINE_TELEMETRY.phase("schedule"):
                    self._sweep_retiring_slots()
            # A compile that landed inside this step delayed every request
            # the step served: attach the events so the HTTP layer can
            # surface them on the victim requests' traces. Compiles in
            # output-less steps (intermediate prefill chunks dispatch
            # without emitting) are held for the next emitting step — the
            # same requests were waiting on them.
            events = (
                self._pending_compile_events
                + ENGINE_TELEMETRY.drain_compile_events()
            )
            if outputs:
                if events:
                    for out in outputs:
                        out.compile_events = list(events)
                self._pending_compile_events = []
            else:
                self._pending_compile_events = events[-8:]  # bounded
            return outputs

    def _schedule(
        self, outputs: List[RequestOutput],
        locked: frozenset = frozenset(),
    ):
        """One scheduling pass and its bookkeeping, as the ``schedule``
        phase; deadline sheds are appended to ``outputs``."""
        with ENGINE_TELEMETRY.phase("schedule"):
            sched = self.scheduler.schedule(locked=locked)
            self.num_preempted_total += len(sched.preempted)
            outputs += self._finish_expired(sched.expired)
        return sched

    def _step_impl(self) -> List[RequestOutput]:
        phase = ENGINE_TELEMETRY.phase
        outputs: List[RequestOutput] = []
        if self.runner.burst_in_flight:
            locked = frozenset(s.request_id for s in self._burst_seqs)
            sched = self._schedule(outputs, locked)
            why, joins = self._chain_break_reason(sched)
            if why is None:
                # The chain goes on, behind the pass's prefill if it made
                # one: that is dispatched first, then the next chained step
                # with the rows the prefill completes among its members
                # (their tokens reach it on the device), and only then is
                # anything fetched, while the new step runs.
                fetched = self._burst_seqs
                self._burst_seqs = members = list(fetched)
                for row, seq, _ in joins:
                    members[row:row + 1] = [seq]  # a dead row, or one more
                handle = None
                if sched.prefills:
                    handle = self.runner.prefill_dispatch(
                        sched.prefills, record_at_fetch=bool(joins))
                    self.chain_kept_prefills_total += 1
                self._count_decode(chained=True, depth=self._burst_n)
                rows = self.runner.burst_continue(members, joins)
                with phase("postprocess", "decode"):
                    outputs += self._process_burst_rows(fetched, rows)
                    self._burst_fetched()
                if handle is not None:
                    # an inner chunk's sample is read by nobody
                    prows = self.runner.prefill_fetch(
                        handle, len(sched.prefills)) if joins else None
                    with phase("postprocess", "prefill"):
                        outputs += self._process_prefill_rows(
                            sched.prefills, prows)
                return outputs
            self.pipeline_breaks[why] += 1
            # The prefill of a pass that drains the chain still slips in
            # BEHIND the in-flight burst: dispatch it first (the device
            # serializes the two), then drain the burst while the prefill
            # executes — one combined wait instead of drain-then-prefill
            # round trips. Safe because the prefill touches only its own
            # freshly-allocated pages (locked members could not be evicted
            # by its allocation).
            prefill_handle = None
            if sched.prefills and not sched.blocked_on_locked:
                prefill_handle = self.runner.prefill_dispatch(sched.prefills)
            rows = self.runner.burst_drain()
            with phase("postprocess", "decode"):
                outputs += self._process_burst_rows(self._burst_seqs, rows)
                self._burst_fetched()
            if prefill_handle is not None:
                prows = self.runner.prefill_fetch(
                    prefill_handle, len(sched.prefills)
                )
                with phase("postprocess", "prefill"):
                    outputs += self._process_prefill_rows(sched.prefills, prows)
                return outputs
        sched = self._schedule(outputs)
        if sched.is_empty:
            return outputs
        if sched.prefills:
            # Intermediate chunks sample nothing anyone reads: dispatch
            # without fetching (the round trip per chunk dominated cold
            # 20k-token prefills). Only a chunk that completes a fresh
            # prompt needs its sampled token back.
            any_completes = any(
                it.end == it.seq.num_prompt_tokens
                and not it.seq.output_token_ids
                for it in sched.prefills
            )
            if any_completes:
                rows = self.runner.execute_prefill_batch(sched.prefills)
            else:
                self.runner.execute_prefill_batch_nofetch(sched.prefills)
                rows = None
            with phase("postprocess", "prefill"):
                outputs += self._process_prefill_rows(sched.prefills, rows)
        elif (
            drafts := self._spec_drafts(sched.decodes, sched.n_decode_steps)
        ) is not None:
            # Speculation first: when it engages it beats a burst on tokens
            # per round trip, and the pipeline below picks up whenever the
            # drafts dry out.
            outputs += self._spec_step(sched.decodes, drafts)
        elif self._pipeline_ok(sched):
            # First burst of a pipeline: dispatch only; its tokens surface
            # on the NEXT step, overlapped with the following burst.
            self._burst_seqs = list(sched.decodes)
            self._burst_n = sched.n_decode_steps
            self._count_decode(chained=True, depth=sched.n_decode_steps)
            self.runner.burst_start(sched.decodes, sched.n_decode_steps)
        elif self.cfg.speculative_mtp:
            # (a row the chain cannot take: near max_model_len, penalties,
            # a guided choice, a standing queue)
            outputs += self._mtp_step(sched.decodes)
        else:
            self._count_decode(chained=False, depth=sched.n_decode_steps)
            bursts = self.runner.execute_decode_multi(
                sched.decodes, sched.n_decode_steps
            )
            with phase("postprocess", "decode"):
                for seq, rows in zip(sched.decodes, bursts):
                    for row in rows:
                        seq.num_computed_tokens += 1
                        self._commit(seq)
                        out = self._append_token(seq, int(row[0]), lp_row=row)
                        if out is not None:
                            outputs.append(out)
                        if seq.is_finished:
                            break  # trim speculative tail of the burst
        return outputs

    # -- speculative decoding (n-gram prompt lookup; engine/spec.py) ----

    def _spec_drafts(
        self, decodes, n_burst: int = 1
    ) -> "Optional[tuple[np.ndarray, np.ndarray]]":
        """Per-sequence draft tokens [B, K] for this decode batch, or None
        when speculation should not engage.

        Gating is PER ROW where possible: only greedy rows get drafts;
        sampled (temperature>0) rows ride the same verify step and have
        position 0 put through the full sampling pipeline — identical to a
        plain decode step for them, and so does a row that asks for
        log-probabilities (the verify step packs position 0's for it). A
        batch-level bail-out remains for penalties (accepted tokens would
        change the counts mid-step), plus too few draft-carrying rows to
        beat a plain burst."""
        K = self.cfg.speculative_ngram
        if not K or not decodes:
            return None
        with ENGINE_TELEMETRY.phase("batch_build", "spec_verify"):
            from .spec import propose_ngram

            for s in decodes:
                if s.sampling.has_penalties:
                    return None
            drafts = np.zeros((len(decodes), K), np.int32)
            lens = np.zeros(len(decodes), np.int32)
            for i, s in enumerate(decodes):
                if (not s.sampling.greedy or s.sampling.guided_choice
                        or s.sampling.logprobs is not None):
                    continue  # rides along; sampled/masked at position 0 only
                if s.num_tokens + K > self.cfg.max_model_len:
                    continue  # verify writes would run past the last page
                d = propose_ngram(
                    self._spec_token_arr(s), K,
                    self.cfg.ngram_min, self.cfg.ngram_max,
                    lookback=self.cfg.ngram_lookback,
                )
                if d:
                    drafts[i, : len(d)] = d
                    lens[i] = len(d)
            # A verify pass costs ~one device round trip; worth it only when
            # enough rows carry drafts — AND when its best case (K+1 tokens per
            # draft row, 1 per other row) beats the n-step burst it replaces
            # (num_decode_steps>1 exists for dispatch-latency-bound setups; a
            # verify pass that yields fewer tokens per round trip would regress
            # exactly there).
            B = len(decodes)
            hits = int(np.count_nonzero(lens))
            if hits * 2 < B or hits * (K + 1) + (B - hits) < n_burst * B:
                return None
            return drafts, lens

    @staticmethod
    def _spec_token_arr(s) -> "np.ndarray":
        """Per-sequence token-id array for the n-gram scan, grown
        incrementally (tokens are append-only) — rebuilding the full list
        and array every decode step was O(context) host work per sequence."""
        total = s.num_tokens
        buf = getattr(s, "_spec_buf", None)
        n = getattr(s, "_spec_buf_n", 0)
        if buf is None or n > total:
            buf = np.empty(max(total * 2, 256), np.int64)
            n = 0
        elif buf.shape[0] < total:
            grown = np.empty(max(total * 2, buf.shape[0] * 2), np.int64)
            grown[:n] = buf[:n]
            buf = grown
        P = s.num_prompt_tokens
        prompt, output = s.prompt_token_ids, s.output_token_ids
        for idx in range(n, total):
            buf[idx] = prompt[idx] if idx < P else output[idx - P]
        s._spec_buf, s._spec_buf_n = buf, total
        return buf[:total]

    def _spec_step(self, decodes, spec) -> List[RequestOutput]:
        """One verify pass: commit each row's accepted draft prefix plus the
        model's own next token (exactly the greedy output)."""
        from .spec import count_accepted

        drafts, lens = spec
        rows, packed0 = self.runner.execute_spec_verify(decodes, drafts)
        with ENGINE_TELEMETRY.phase("postprocess", "spec_verify"):
            outputs: List[RequestOutput] = []
            for i, seq in enumerate(decodes):
                lp_row = None
                if lens[i] == 0:
                    # Draftless (or sampled) row: position 0 went through the
                    # full sampling pipeline — exactly one plain decode step,
                    # its log-probabilities packed where a row asks for them.
                    emitted, lp_row = [int(packed0[i][0])], packed0[i]
                else:
                    draft = [int(t) for t in drafts[i][: lens[i]]]
                    a = count_accepted(draft, rows[i])
                    # Clamp: never emit past max_model_len.
                    a = min(a, self.cfg.max_model_len - seq.num_tokens - 1)
                    self.spec_proposed_total += len(draft)
                    self.spec_accepted_total += a
                    emitted = draft[:a] + [int(rows[i][a])]
                for tok in emitted:
                    seq.num_computed_tokens += 1
                    self._commit(seq)
                    out = self._append_token(seq, tok, lp_row=lp_row)
                    if out is not None:
                        outputs.append(out)
                    if seq.is_finished:
                        break
            return outputs

    def _mtp_step(self, decodes) -> List[RequestOutput]:
        """One verify-and-draft step outside a chain (``--speculative-mtp``
        with a row the chain cannot take, or ``--no-overlap-decode``): each
        row commits the model's own next token and, where the device accepted
        its draft, the one after. The accept test ran on the device; what is
        left here is what every fetched decode step's rows get."""
        self._count_decode(chained=False, depth=1)
        rows = self.runner.execute_mtp_verify(decodes)
        with ENGINE_TELEMETRY.phase("postprocess", "decode"):
            return self._process_burst_rows(decodes, rows)

    def _finish_expired(self, expired) -> List[RequestOutput]:
        """Surface scheduler deadline sheds to their waiting clients: the
        sequence is already finished (pages released, finish_reason
        "deadline"); emit the terminal RequestOutput so the HTTP layer can
        answer 504 (non-streaming) or close the stream (streaming)."""
        outs: List[RequestOutput] = []
        for seq in expired:
            if seq.request_id not in self._seqs:
                continue
            self._seqs.pop(seq.request_id, None)
            self._detok.pop(seq.request_id, None)
            outs.append(
                RequestOutput(
                    request_id=seq.request_id,
                    finished=True,
                    finish_reason="deadline",
                    num_prompt_tokens=seq.num_prompt_tokens,
                    num_output_tokens=len(seq.output_token_ids),
                    num_cached_prompt_tokens=seq.num_cached_prompt_tokens,
                    # Shed work still consumed device time: bill it.
                    cost=self._finalize_cost(seq),
                )
            )
        return outs

    def _process_prefill_rows(self, prefills, rows) -> List[RequestOutput]:
        """``rows is None`` for dispatch-only steps (no chunk completed a
        fresh prompt, so there is no sampled token to read)."""
        outputs: List[RequestOutput] = []
        for i, item in enumerate(prefills):
            seq = item.seq
            seq.num_computed_tokens = item.end
            # a row that joined the chain behind this prefill is written
            # through by the burst in flight already
            self._commit(seq, allow_swap=not (
                self.runner.burst_in_flight and seq in self._burst_seqs))
            # Streamed disagg handoff: this chunk's freshly committed
            # pages go out NOW, overlapped with the next chunk's compute
            # (docs/disagg.md) — not serially after the prefill response.
            self._stream_publish(
                seq, prefill_complete=item.end == seq.num_prompt_tokens
            )
            # Sample only when this chunk completes a *fresh* prompt;
            # recompute chunks (post-preemption) must not re-emit tokens.
            if item.end == seq.num_prompt_tokens and not seq.output_token_ids:
                assert rows is not None, "completing chunk needs its token"
                out = self._append_token(seq, int(rows[i][0]), lp_row=rows[i])
                if out is not None:
                    outputs.append(out)
        return outputs

    def _stream_publish(self, seq: Sequence, prefill_complete: bool) -> None:
        """Hand ``seq``'s newly committed pages to the handoff publisher
        (step-thread cost: device→host download + a deque append; all DCN
        runs on the publisher's worker thread). The completion marker —
        the decode side's "last block" signal — carries the full-block
        count of the prompt, which is exactly what the consumer's
        match_prefix can adopt."""
        pub = self.kv_publisher
        transfer = seq.kv_transfer
        if pub is None or not transfer:
            return
        if transfer.get("role") == "consumer":
            # The decode leg on a kv_role="both" engine: its prompt blocks
            # were just PREFETCHED from the store — re-publishing them
            # would re-download every page on the step thread and break
            # the one-copy-per-page contract.
            return
        rid = transfer.get("request_id")
        if not rid:
            return
        n = seq._committed_blocks
        if n > seq.kv_published_cursor:
            pages = []
            for i in range(seq.kv_published_cursor, n):
                k, v = self.runner.download_page(seq.block_ids[i])
                pages.append((seq.block_hashes[i], k, v))
            pub.publish(rid, pages)
            self.kv_published_blocks_total += len(pages)
            seq.kv_published_cursor = n
        if prefill_complete and not transfer.get("_completed"):
            transfer["_completed"] = True
            pub.complete(
                rid, seq.num_prompt_tokens // self.cfg.block_size
            )

    # -- pipelined decode internals ------------------------------------

    def _pipeline_ok(self, sched) -> bool:
        """May this pass start a chain? Whenever its decode batch can be
        chained: ``overlap_decode`` on (the default), no n-gram speculation
        configured, every row chainable and no queue left standing
        (`_queue_stands`). It does not ask whether requests are arriving: an
        arrival waits for the one burst in flight, as it waits for the
        running step in the synchronous loop, its prefill is launched
        behind that burst and the chain goes on behind the prefill with the
        arrival among its rows (`_step_impl`, `_chain_break_reason`)."""
        if not sched.decodes or not self.cfg.overlap_decode:
            return False
        # Speculation and overlap are alternative round-trip amortizers;
        # when n-gram speculation is configured it wins outright (more
        # tokens per trip for greedy rows) and overlap stays out of its
        # way.
        if self.cfg.speculative_ngram:
            return False
        return (
            self._chainable(sched.decodes, sched.n_decode_steps)
            and not self._queue_stands()
        )

    def _chainable(self, decodes, n: int) -> bool:
        """Can every row ride a chain of depth ``n``? Not a guided row (its
        allowed-token mask is rebuilt per token host-side; penalty rows
        ride, their state lives in the scan carry), not a row within two
        bursts of ``max_model_len`` (a continuation writes up to
        num_tokens + 2n, the host's view lagging one burst; past the limit
        its pages would not exist), not one whose deadline has passed (the
        scheduler sheds only what no burst in flight writes through)."""
        now = time.monotonic()
        # A chained verify-and-draft step advances a row by up to two and
        # writes one slot past its draft's: twice the room; its program
        # carries no penalty counts.
        mtp = bool(self.cfg.speculative_mtp)
        room = 2 * n * (2 if mtp else 1)
        return not any(
            s.sampling.guided_choice
            or s.num_tokens + room > self.cfg.max_model_len
            or s.deadline_expired(now)
            or (mtp and s.sampling.has_penalties)
            for s in decodes
        )

    def _queue_stands(self) -> bool:
        """Did the pass just made leave requests waiting or parked (every
        row taken, no pages)? What the scheduler does for a standing queue
        needs rows no burst writes through: timeslicing rotates only
        unlocked rows, batch-tier preemption and deadline sheds skip locked
        ones, and members that finished mid-chain give their pages back at
        the drain. So the loop stays synchronous while a queue stands, as
        it was before a chain could start under arrivals; a request the
        pass admitted is a prefill, not a queue."""
        return bool(self.scheduler.num_waiting or self.scheduler.num_swapped)

    def _chain_break_reason(self, sched) -> tuple:
        """``(None, joins)`` while the burst in flight may chain: the NEXT
        burst's rows are the chain's live members and the sequences whose
        prompt this pass's prefill completes (``joins``: ``(row, sequence,
        prefill row)`` each, a row that is free or whose member finished),
        its shape is the chain's and its writes are provably covered. Else
        ``(why, ())``, by what the pass made under the chain's locks
        produced (the label of ``pst:pipeline_breaks_total``).

        A decode pass has scheduled and reserved pages for exactly the live
        members. A prefill pass returns before the scheduler's decode
        phase, so what that phase would have said is asked here: the
        configured depth, every row chainable, a row and
        the chain's sampling program for each new member, and pages for the
        next burst of all (`Scheduler.reserve_chain`). A prefill step that
        completes no prompt (an inner chunk) changes no membership."""
        alive = [s for s in self._burst_seqs if not s.is_finished]
        if sched.blocked_on_locked:
            return "blocked_on_locked", ()
        fresh = []
        for it in sched.prefills:
            if it.seq.output_token_ids:
                if it.end >= it.seq.num_tokens - 1:
                    # a preempted sequence recomputed: its next token is
                    # the host's, and the restart takes it from there
                    return "prefill", ()
            elif it.end == it.seq.num_prompt_tokens:
                fresh.append(it.seq)
        members = alive + fresh
        if sched.prefills:
            n = max(self.cfg.num_decode_steps, 1)
            if not members:
                return "decode_set", ()
        else:
            n = sched.n_decode_steps
            if not alive or len(sched.decodes) != len(alive) or (
                {id(s) for s in sched.decodes} != {id(s) for s in alive}
            ):
                return "decode_set", ()
        if n != self._burst_n:
            return "depth", ()
        if self._queue_stands():
            return "queue", ()
        if not self._chainable(members, self._burst_n):
            return "not_eligible", ()
        free = [i for i, s in enumerate(self._burst_seqs) if s.is_finished]
        free += range(len(self._burst_seqs), self.runner.burst_rows())
        if len(fresh) > len(free):
            return "row_bucket", ()
        if fresh and not self.runner.burst_variant_fits(fresh):
            return "sampling_variant", ()
        if sched.prefills:
            evicted = len(sched.preempted)
            fits = self.scheduler.reserve_chain(members, self._burst_n, sched)
            self.num_preempted_total += len(sched.preempted) - evicted
            if not fits:
                return "blocked_on_locked", ()
        if not self.runner.burst_width_stable(members):
            return "table_width", ()
        # after the reservation: it may have taken a chunk out of the pass
        at = {id(it.seq): i for i, it in enumerate(sched.prefills)}
        return None, [
            (row, seq, at[id(seq)]) for row, seq in zip(free, fresh)]

    def _count_decode(self, chained: bool, depth: int) -> None:
        self.decode_dispatches_total += 1
        self.decode_layer_passes_total += self.runner.layers_a_token * depth
        if chained:
            self.pipelined_bursts_total += 1

    def _process_burst_rows(self, members, rows) -> List[RequestOutput]:
        """Apply one fetched burst's tokens. Rows align with ``members``,
        the membership that burst was dispatched with; rows of members
        that finished earlier are speculative garbage and are skipped.
        While another burst is still in flight, page releases and dedup
        swaps are deferred — the device writes through these page ids."""
        outputs: List[RequestOutput] = []
        inflight = self.runner.burst_in_flight
        for seq, seq_rows in zip(members, rows):
            if seq.is_finished:
                continue
            for row in seq_rows:
                seq.num_computed_tokens += 1
                self._commit(seq, allow_swap=not inflight)
                out = self._append_token(seq, int(row[0]), lp_row=row)
                if out is not None:
                    outputs.append(out)
                if seq.is_finished:
                    break  # trim speculative tail of the burst
        if not inflight:
            self._burst_seqs = []
            self._burst_n = 0
        return outputs

    def _defer_release(self, seq: Sequence) -> None:
        """``seq`` left a chain whose bursts write through its pages (and
        state slot, and window pages): it gives them back once the last
        burst dispatched so far has been fetched. The next one is told the
        row is dead and writes nothing."""
        self._burst_deferred.append((self.pipelined_bursts_total, seq))

    def _burst_fetched(self) -> None:
        """One more chained burst has been fetched (or dropped): release
        what no burst still in flight writes through. A chain that never
        drains gives its finished members' pages back here, a burst after
        each finished."""
        self._bursts_fetched += 1
        if not self._burst_deferred:
            return
        held = []
        for last_writer, seq in self._burst_deferred:
            if last_writer <= self._bursts_fetched:
                self.allocator.release_sequence(seq)
            else:
                held.append((last_writer, seq))
        self._burst_deferred = held

    # Controller-registration hygiene: chunk claims older than the TTL (or
    # beyond the cap) are dropped so KV-aware routing doesn't chase KV that
    # LRU eviction already reclaimed, and the dict can't grow unboundedly.
    CHUNK_CLAIM_TTL = 20 * 60.0
    CHUNK_CLAIM_CAP = 200_000

    def _commit(self, seq: Sequence, allow_swap: bool = True) -> None:
        seq.commit_full_blocks(self.allocator, allow_swap=allow_swap)
        now = time.time()
        for h in seq.commit_full_chunks(CHUNK_TOKENS):
            self.resident_chunk_hashes.pop(h, None)  # refresh insertion order
            self.resident_chunk_hashes[h] = now
        if len(self.resident_chunk_hashes) > self.CHUNK_CLAIM_CAP:
            self._prune_chunk_claims(now)

    def _prune_chunk_claims(self, now: float) -> None:
        cutoff = now - self.CHUNK_CLAIM_TTL
        fresh = {h: t for h, t in self.resident_chunk_hashes.items() if t >= cutoff}
        if len(fresh) > self.CHUNK_CLAIM_CAP:
            # insertion order == recency (refreshed on re-commit): keep newest
            fresh = dict(list(fresh.items())[-self.CHUNK_CLAIM_CAP :])
        self.resident_chunk_hashes = fresh

    def _push_kv_to_remote(self, seq: Sequence) -> int:
        """Producer-side finish push: ship whatever committed pages the
        streamed publisher has NOT already sent (``kv_published_cursor``)
        in one batched round trip — the legacy role-based disagg path for
        requests without ``kv_transfer_params``, and the tail (decode-
        produced blocks) for streamed ones. One copy per page, ever."""
        remote = getattr(self.allocator, "remote", None)
        if remote is None:
            return 0
        start = seq.kv_published_cursor
        if seq.kv_transfer and seq.kv_transfer.get("role") == "consumer":
            # A consumer leg's cached prompt prefix CAME from the store
            # (the prefetch) — only blocks computed here are new.
            start = max(
                start, seq.num_cached_prompt_tokens // self.cfg.block_size
            )
        pages = [
            (h, *self.runner.download_page(blk))
            for blk, h in zip(seq.block_ids[start:], seq.block_hashes[start:])
        ]
        if not pages or not remote.put_blocks(pages):
            return 0
        seq.kv_published_cursor = start + len(pages)
        return len(pages)

    # ------------------------------------------------------------------
    # Token bookkeeping
    # ------------------------------------------------------------------

    def _append_token(
        self, seq: Sequence, token: int, lp_row=None
    ) -> Optional[RequestOutput]:
        sp = seq.sampling
        seq.output_token_ids.append(token)
        self.generation_tokens_total += 1
        now = time.monotonic()  # same clock as arrival_time (sequence.py)
        if seq.first_token_time is None:
            seq.first_token_time = now

        finish_reason: Optional[str] = None
        is_stop_token = False
        if not sp.ignore_eos and token in self.model_cfg.eos_token_ids:
            finish_reason = "stop"
            is_stop_token = True
        elif token in sp.stop_token_ids:
            finish_reason = "stop"
            is_stop_token = True
        elif sp.guided_done(seq.output_token_ids):
            finish_reason = "stop"  # output IS one of the guided choices
        elif len(seq.output_token_ids) >= sp.max_tokens:
            finish_reason = "length"
        elif seq.num_tokens >= self.cfg.max_model_len:
            finish_reason = "length"

        # Incremental detokenization: decode only a sliding window of recent
        # tokens (O(window) per step, not O(total)); hold back text while the
        # window ends in a partial multi-byte/multi-token character.
        delta = "" if is_stop_token else self._detok_delta(seq)
        st = self._detok[seq.request_id]
        if delta and sp.stop_strings():
            emitted = st["emitted"]
            full = emitted + delta
            for stop_s in sp.stop_strings():
                idx = full.find(stop_s, max(len(emitted) - len(stop_s), 0))
                if idx >= 0:
                    delta = full[:idx][len(emitted):]
                    finish_reason = "stop"
                    break
        st["emitted"] += delta

        logprobs_entry = None
        if (
            sp.logprobs is not None
            and lp_row is not None
            and lp_row.shape[-1] > 1  # width-1 rows: compiled without logprobs
        ):
            from ..ops.sampling import unpack_sampled

            _, chosen, top_lps, top_ids = unpack_sampled(lp_row)
            k = min(int(sp.logprobs), top_ids.shape[-1])
            logprobs_entry = {
                "token_id": token,
                "logprob": float(chosen),
                "top": [
                    (int(top_ids[j]), float(top_lps[j])) for j in range(k)
                ],
            }

        scheduled = seq.first_scheduled_time
        out = RequestOutput(
            request_id=seq.request_id,
            text_delta=delta,
            new_token_ids=[token],
            num_prompt_tokens=seq.num_prompt_tokens,
            num_output_tokens=len(seq.output_token_ids),
            num_cached_prompt_tokens=seq.num_cached_prompt_tokens,
            ttft=(seq.first_token_time - seq.arrival_time),
            queue_time=(
                scheduled - seq.arrival_time if scheduled is not None else None
            ),
            prefill_time=(
                seq.first_token_time - scheduled
                if scheduled is not None else None
            ),
            first_token_time=seq.first_token_time,
            logprobs=[logprobs_entry] if logprobs_entry else None,
        )
        if finish_reason is not None:
            out.decode_time = now - seq.first_token_time
            # Cost account closes while the pages are still owned (the
            # scheduler releases them just below).
            out.cost = self._finalize_cost(seq)
            if self.cfg.kv_role in ("producer", "both"):
                sent = self._push_kv_to_remote(seq)
                if sent:
                    logger.debug(
                        "disagg: pushed %d KV pages for %s", sent, seq.request_id
                    )
            if self.runner.burst_in_flight and seq in self._burst_seqs:
                # The in-flight burst still writes through this sequence's
                # pages: detach now, release once that burst is fetched.
                self.scheduler.detach(seq.request_id, finish_reason)
                self._defer_release(seq)
            else:
                self.scheduler.finish(seq, finish_reason)
            out.finished = True
            out.finish_reason = finish_reason
            self._seqs.pop(seq.request_id, None)
            self._detok.pop(seq.request_id, None)
        return out

    def _detok_delta(self, seq: Sequence) -> str:
        """vLLM-style incremental detokenization over a bounded window."""
        st = self._detok[seq.request_id]
        ids = seq.output_token_ids
        prefix, read = int(st["prefix"]), int(st["read"])  # type: ignore[arg-type]
        prefix_text = self.tokenizer.decode(ids[prefix:read])
        new_text = self.tokenizer.decode(ids[prefix:])
        if new_text.endswith("�") and len(ids) - read < 16:
            return ""  # partial character: hold until it completes (bounded —
            # genuinely invalid byte runs are force-emitted after 16 tokens)
        delta = new_text[len(prefix_text):]
        st["prefix"], st["read"] = read, len(ids)
        return delta

    # ------------------------------------------------------------------
    # Convenience (tests / bench)
    # ------------------------------------------------------------------

    def generate(
        self,
        prompts: Union[List[str], List[List[int]]],
        sampling: Optional[SamplingParams] = None,
    ) -> List[Dict[str, object]]:
        """Run prompts to completion; returns list of dicts with text/ids."""
        results: Dict[str, Dict[str, object]] = {}
        for i, p in enumerate(prompts):
            rid = f"gen-{i}"
            kwargs = {"prompt_token_ids": p} if isinstance(p, list) else {"prompt": p}
            self.add_request(rid, sampling=sampling, **kwargs)
            results[rid] = {"text": "", "token_ids": [], "finish_reason": None}
        while self.has_work():
            for out in self.step():
                r = results[out.request_id]
                r["text"] = str(r["text"]) + out.text_delta
                r["token_ids"].extend(out.new_token_ids)  # type: ignore[union-attr]
                if out.finished:
                    r["finish_reason"] = out.finish_reason
        return [results[f"gen-{i}"] for i in range(len(prompts))]

    # ------------------------------------------------------------------
    # Metrics snapshot for the server layer
    # ------------------------------------------------------------------

    def _pool_groups(self) -> dict:
        """What the allocator owns beside the global pages: the state slots
        of a model with recurrent layers, the page group of one with
        sliding-window layers."""
        out = {"state_slots": self.runner.state_slots}
        if self.runner.window_blocks:
            out["window_blocks"] = self.runner.window_blocks
            out["window_tokens"] = self.runner.model_cfg.sliding_window
        return out

    def stats(self) -> Dict[str, float]:
        out = {
            "num_requests_running": float(self.scheduler.num_running),
            "num_requests_waiting": float(self.scheduler.num_waiting),
            "num_requests_swapped": float(self.scheduler.num_swapped),
            "num_preemptions_total": float(self.num_preempted_total),
            "prompt_tokens_total": float(self.prompt_tokens_total),
            "generation_tokens_total": float(self.generation_tokens_total),
            "kv_cache_usage_perc": self.allocator.usage,
            "prefix_cache_hit_rate": self.allocator.hit_rate,
            "prefix_cache_hits_total": float(self.allocator.hit_tokens),
            "prefix_cache_queries_total": float(self.allocator.query_tokens),
            "deadline_sheds_queued_total": float(
                self.scheduler.deadline_sheds_queued
            ),
            "deadline_sheds_running_total": float(
                self.scheduler.deadline_sheds_running
            ),
            # Cost-attribution audit scalar (docs/observability.md "Cost
            # attribution"): live-traffic device-busy wall; finished
            # request costs must sum to >= 90% of this.
            "device_busy_seconds_total": ENGINE_TELEMETRY.device_busy_seconds(),
        }
        if self.cfg.tenant_fairness:
            ages = self.scheduler.queue_age_by_tier()
            out["tenant_queue_age_interactive"] = ages["interactive"]
            out["tenant_queue_age_batch"] = ages["batch"]
            out["tenant_batch_preemptions_total"] = float(
                self.scheduler.batch_preemptions
            )
        if self.cfg.speculative_ngram:
            out["spec_decode_num_draft_tokens_total"] = float(
                self.spec_proposed_total
            )
            out["spec_decode_num_accepted_tokens_total"] = float(
                self.spec_accepted_total
            )
        out["decode_dispatches_total"] = float(self.decode_dispatches_total)
        out["decode_layer_passes_total"] = float(
            self.decode_layer_passes_total)
        out["prefill_layer_passes_total"] = float(
            self.runner.prefill_layer_passes_total)
        out["decode_context_tokens_total"] = float(
            self.runner.decode_context_tokens_total)
        out["decode_shared_tokens_spared_total"] = float(
            self.runner.decode_shared_tokens_spared_total)
        out["kv_slot_layers"] = float(self.model_cfg.num_kv_layers)
        out["prefix_waits_total"] = float(self.scheduler.prefix_waits)
        if self.runner.state_slots:
            out["state_slots_in_use"] = float(self.allocator.state_slots_in_use)
            out["state_slot_waits_total"] = float(
                self.allocator.state_slot_waits
            )
        out["kv_pages_in_use"] = float(
            self.allocator.num_blocks - self.allocator.num_free)
        out["prefill_tokens_total"] = float(self.runner.prefill_tokens_total)
        out["prefill_bucket_positions_total"] = float(
            self.runner.prefill_bucket_positions_total)
        if self.runner.window_blocks:
            out["window_pages_in_use"] = float(
                self.allocator.window_pages_in_use)
            out["window_pages_released_total"] = float(
                self.allocator.window_pages_released)
            out["window_pages_cached"] = float(
                self.allocator.window_pages_cached)
            out["window_prefix_tokens_lost_total"] = float(
                self.allocator.window_prefix_tokens_lost)
            out["window_pages_evicted_total"] = float(
                self.allocator.window_pages_evicted)
            out["window_page_steps_total"] = float(
                self.runner.window_page_steps_total)
            out["window_whole_context_page_steps_total"] = float(
                self.runner.window_whole_context_page_steps_total)
        # what the model's steps reported, under the model's own names
        for name, total in zip(self.runner.aux_names,
                               self.runner.step_aux_totals):
            out[name] = float(total)
        if self.cfg.overlap_decode:
            out["pipelined_bursts_total"] = float(self.pipelined_bursts_total)
            out["pipeline_breaks_total"] = dict(self.pipeline_breaks)
            out["chain_kept_prefills_total"] = float(
                self.chain_kept_prefills_total)
        # Tiering KPIs (present when the LMCache-analogue layer is on).
        for attr in ("host_hit_blocks", "remote_hit_blocks", "spilled_blocks"):
            if hasattr(self.allocator, attr):
                out[f"kv_offload_{attr}"] = float(getattr(self.allocator, attr))
        # Streamed disagg handoff KPIs (docs/disagg.md).
        if self.kv_publisher is not None or self.kv_prefetcher is not None:
            out["kv_published_blocks_total"] = float(
                self.kv_published_blocks_total
            )
        if self.kv_publisher is not None:
            out["kv_publish_failures_total"] = float(
                self.kv_publisher.publish_failures
            )
        if self.kv_prefetcher is not None:
            out["kv_prefetched_blocks_total"] = float(
                self.kv_prefetcher.prefetched_blocks
            )
            out["kv_transfer_fallbacks_total"] = float(
                self.kv_prefetcher.fallbacks
            )
        # Remote-tier integrity/replication audit (docs/kvserver.md):
        # digest-verification failures, replica read-repairs and GET
        # retries, counted in the KV client (plain or sharded).
        remote_client = getattr(self.allocator, "remote", None)
        if remote_client is not None and hasattr(remote_client, "counters"):
            if hasattr(remote_client, "refresh_counters"):
                remote_client.refresh_counters()
            counters = remote_client.counters
            out["kv_integrity_failures_total"] = float(
                counters.get("integrity_failures", 0)
            )
            out["kv_read_repairs_total"] = float(
                counters.get("read_repairs", 0)
            )
            out["kv_remote_retries_total"] = float(
                counters.get("retries", 0)
            )
        if self.swapper is not None:
            out["kv_swap_out_total"] = float(self.swapper.swap_out_total)
            out["kv_swap_in_total"] = float(self.swapper.swap_in_total)
            out["kv_swap_tail_pages_total"] = float(
                self.swapper.tail_pages_moved
            )
            out["kv_swap_fallback_recompute_total"] = float(
                self.swapper.fallback_recompute_total
            )
            out["kv_swap_stash_blocks"] = float(self.swapper.stash_blocks)
        return out
