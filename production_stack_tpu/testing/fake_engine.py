"""Fake serving engine: the backend for router tests without TPUs.

Capability parity with the reference's
``src/tests/perftest/fake-openai-server.py`` (streams tokens at a
configurable rate, tracks running-request count) extended to the full
surface the router depends on (SURVEY.md §4 "pattern to replicate"):
``/v1/models``, ``/v1/chat/completions``, ``/v1/completions`` (streaming
and non-streaming), ``/metrics`` with ``vllm:``-style gauges,
``/is_sleeping`` + ``/sleep`` + ``/wake_up``, ``/health``, ``/ready``
(simulated warmup precompilation: ``--ready-delay`` + a warm-restart
cache-dir marker), LoRA load/unload endpoints, and ``/tokenize``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import re
import time
import uuid
from collections import OrderedDict
from typing import List, Optional

import aiohttp
import xxhash
from aiohttp import web

from ..logging_utils import init_logger
from ..obs import (
    bind_log_context,
    configure_logging,
    observe_stage,
    parse_traceparent,
    render_obs_metrics,
    unbind_log_context,
)
from ..obs.flight import FIELDS as FLIGHT_FIELDS
from ..obs.flight import STALL_CAUSES

logger = init_logger(__name__)

# Simulated lattice size: what the warmup metrics (coverage, cache
# hits/misses) count against. Arbitrary but deterministic.
FAKE_WARMUP_BUCKETS = 12

# Fraction of the cold ready delay a warm restart pays (the persistent
# cache skips XLA but tracing/deserialization still cost something).
_WARM_RESTART_FRACTION = 0.2

# Simulated prefix-cache granularity: chars per KV chunk and the token
# mass one chunk represents (~the real engine's block-size granularity;
# the fake "tokenizer" is ~4 chars/token).
KV_CHUNK_CHARS = 32
KV_CHUNK_TOKENS = 8
# Working KV a running sequence holds beyond its cached prefix (rough —
# drives occupancy up under concurrency the way live decode state does).
KV_RUNNING_TOKENS = 64


def kv_chunk_hashes(text: str) -> List[int]:
    """Prefix-committing chain hashes over fixed char windows: chunk i's
    hash commits to everything before it, so a match on chunk i implies
    the whole prefix matches — the same property the real chunk-hash
    scheme (kvcache/hashing.py) has."""
    out: List[int] = []
    h = 0
    for i in range(0, len(text), KV_CHUNK_CHARS):
        h = xxhash.xxh64_intdigest(f"{h:x}:{text[i:i + KV_CHUNK_CHARS]}")
        out.append(h)
    return out


class FakeEngineState:
    def __init__(self, model: str, speed: float, max_tokens_default: int = 32,
                 kv_capacity_tokens: int = 20000, kv_url: Optional[str] = None,
                 kv_replication: int = 2):
        self.model = model
        self.speed = speed  # tokens per second
        self.max_tokens_default = max_tokens_default
        # Streamed disagg KV handoff (docs/disagg.md): with a kvserver URL
        # configured, a producer-leg generation publishes deterministic
        # block manifests + pages per simulated prefill chunk, and a
        # consumer-leg generation follows the manifest and batch-fetches
        # them BEFORE decoding — the real handoff protocol without TPUs.
        # A comma-separated URL list makes this a sharded-ring client with
        # the same placement/replication/read-repair semantics as the real
        # engine's ShardedKVClient (docs/kvserver.md) — what the
        # kv_shard_kill chaos leg drives.
        self.kv_urls = [
            u.strip().rstrip("/")
            for u in (kv_url or "").split(",") if u.strip()
        ]
        self.kv_url = self.kv_urls[0] if self.kv_urls else None
        self.kv_replication = (
            min(max(int(kv_replication), 1), len(self.kv_urls))
            if self.kv_urls else 0
        )
        self.kv_ring = None
        if len(self.kv_urls) > 1:
            from ..hashring import ConsistentHashRing

            self.kv_ring = ConsistentHashRing()
            self.kv_ring.update(self.kv_urls)
        self.kv_transfer_timeout = 5.0
        self.kv_published_blocks = 0
        self.kv_prefetched_blocks = 0
        self.kv_transfer_fallbacks = 0
        self.kv_read_repairs = 0
        self.kv_integrity_failures = 0

        self.manifest_fetches = 0
        self.kv_publish_chunks = 3  # simulated prefill chunk count
        self.kv_chunk_delay = 0.02  # seconds between chunk publishes
        # Opt-in chip queueing model (--chip-ms-per-ktok; bench's disagg
        # phase): one "chip" per engine processes slices FIFO — a prefill
        # is one big exclusive slice (this many ms per 1000 prompt
        # tokens), each decode token a small one. On a fused engine every
        # prefill queues behind in-flight decode slices and vice versa —
        # exactly the head-of-line interference P/D disaggregation
        # removes. A consumer leg whose prefetch completed pays only a
        # tail slice (10%): its prefix KV arrived over the wire. 0 = off
        # (the legacy instant-concurrency behavior every other test
        # relies on).
        self.chip_ms_per_ktok = 0.0
        self.num_running = 0
        self.num_waiting = 0
        # Token-weighted prefix-cache accounting, fed by the simulated
        # paged KV below (was: hardcoded zeros) — hit rate really reflects
        # whether this engine served this conversation before.
        self.prefix_hits = 0
        self.prefix_queries = 0
        # Simulated paged KV cache: chunk hash -> token mass, LRU order.
        # Occupancy derives from what is actually cached + running, so
        # routing tests exercise real headroom dynamics instead of
        # min(1, num_running * 0.1).
        self.kv_capacity_tokens = max(int(kv_capacity_tokens), 1)
        self.kv_chunks: "OrderedDict[int, int]" = OrderedDict()
        self.kv_tokens = 0
        # /admin/fill_kv: reported-occupancy floor for headroom-spill
        # tests that need an engine pinned "full" without traffic.
        self.kv_fill_floor = 0.0
        self.sleeping = False
        self.sleep_level: Optional[str] = None
        self.lora_adapters: List[str] = []
        self.requests_seen: List[dict] = []
        # Fault injection (resilience tests): POST /admin/fail arms one of
        #   error — respond fail_status (default 500) immediately
        #   transfer — break the disagg KV handoff only: a producer leg
        #           publishes nothing (its manifest never completes) and a
        #           consumer leg finds nothing — both degrade to the fused
        #           path and count kv_transfer_fallbacks; the generation
        #           itself still succeeds (no client-visible error)
        #   hang  — accept the request and never answer
        #   midstream — stream fail_after_chunks delta chunks, then drop
        #               the connection (tests the never-replay-after-
        #               first-byte rule and stream resumption; 0 = die
        #               before any delta, >= max_tokens = die after the
        #               last delta but before [DONE])
        #   slow  — inject fail_delay (+ up to fail_jitter) seconds of
        #           latency before answering, honoring the propagated
        #           X-PST-Deadline-Ms budget: when the injected delay would
        #           blow the budget, reply 504 + X-PST-Deadline-Exceeded at
        #           the deadline instead (deterministic hedging/shedding
        #           tests)
        # fail_count > 0 limits the fault to the next N generations
        # (auto-heal); -1 = until POST /admin/heal.
        # fail_tenant scopes the fault to requests carrying that
        # X-PST-Tenant value (isolation chaos legs fault one tenant's
        # traffic without touching the victim's; None = every request).
        self.fail_mode: Optional[str] = None
        self.fail_status = 500
        self.fail_count = -1
        self.fail_delay = 0.5
        self.fail_jitter = 0.0
        self.fail_tenant: Optional[str] = None
        # Delta chunks delivered before a `midstream` death (default 3,
        # the legacy hardcoded behavior).
        self.fail_after_chunks = 3
        self.num_faulted = 0
        # Graceful drain: new generations 503, in-flight ones finish.
        self.draining = False
        # X-PST-Deadline-Ms header value (or None) per generation request,
        # in arrival order — lets tests assert budget propagation/decay.
        self.deadlines_seen: List[Optional[str]] = []
        # (traceparent, X-Request-Id) per generation request, in arrival
        # order — lets e2e tests assert one trace id spans every leg
        # (primary, retries, hedges) across engines.
        self.traces_seen: List[dict] = []
        # (X-PST-Tenant, X-PST-Tenant-Class) per generation request, in
        # arrival order — lets tests assert the router's tenant stamp
        # reached the engine on every hop.
        self.tenants_seen: List[dict] = []
        # Deterministic flight-recorder ring (the real engine's
        # GET /debug/flight contract, docs/observability.md "Flight
        # recorder"): every generation appends one prefill + one decode
        # record with values derived from the request, so router-side
        # flight/capacity tests run engine-free and byte-reproducibly.
        self.flight_records: List[dict] = []
        self.flight_capacity = 128
        self.flight_total = 0
        # Retained flight snapshots (the real recorder's snapshot_log
        # contract): the `stall` fault appends a deterministic
        # tail_outlier snapshot naming the stalled step's bucket and
        # queue depths, so flight-snapshot tests induce a stalled
        # step on CPU. With a flight_snapshot_dir set, each
        # snapshot is also persisted (same file naming as
        # obs/flight.py) so post-mortem collection works after SIGKILL.
        self.flight_snapshots: List[dict] = []
        # pst_engine_stalls_total / pst_engine_stall_seconds_total
        # {cause="device"}: what the injected stalls have left.
        self.stalls = 0
        self.stall_seconds = 0.0
        self.flight_snapshot_keep = 8
        self.flight_snapshot_dir: Optional[str] = None
        self.restored_snapshots: List[dict] = []
        self._snapshot_seq = 0
        # Simulated warmup precompilation (the real engine's /ready
        # contract): the engine reports warming for ``ready_delay``
        # seconds after start. With a ``warmup_cache_dir``, a marker file
        # left by a previous instance makes this a WARM restart — the
        # delay shrinks to a fraction and the deterministic cache
        # counters flip from all-misses to all-hits, so router-discovery
        # and restart e2e tests run the full story without a TPU.
        self.ready_delay = 0.0
        self.warmup_cache_dir: Optional[str] = None
        self.warm_start = False
        self.warmup_started = time.monotonic()
        self._marker_written = False

    def kv_owners(self, key) -> List[str]:
        """A block/manifest key's R-member replica owner set (the whole
        "fleet" when single-shard — identical to the pre-ring behavior)."""
        if self.kv_ring is None:
            return list(self.kv_urls)
        return self.kv_ring.get_nodes(str(key), self.kv_replication)

    def kv_walk(self, key) -> List[str]:
        """Ring-order read walk (owners first, then every other shard)."""
        if self.kv_ring is None:
            return list(self.kv_urls)
        return self.kv_ring.get_nodes(str(key), len(self.kv_urls))

    def configure_warmup(
        self, ready_delay: float, cache_dir: Optional[str] = None
    ) -> None:
        self.ready_delay = max(float(ready_delay), 0.0)
        self.warmup_cache_dir = cache_dir
        self.warm_start = bool(
            cache_dir and os.path.exists(os.path.join(cache_dir, "warm"))
        )
        self.warmup_started = time.monotonic()
        self._marker_written = False

    @property
    def effective_ready_delay(self) -> float:
        return self.ready_delay * (
            _WARM_RESTART_FRACTION if self.warm_start else 1.0
        )

    @property
    def warming(self) -> bool:
        warming = (
            time.monotonic() - self.warmup_started
            < self.effective_ready_delay
        )
        if not warming and self.warmup_cache_dir and not self._marker_written:
            # Ready (first observation): persist the cache marker once so
            # the next instance with this cache dir restarts warm (the
            # PVC/hostPath analogue).
            self._marker_written = True
            try:
                os.makedirs(self.warmup_cache_dir, exist_ok=True)
                with open(
                    os.path.join(self.warmup_cache_dir, "warm"), "w"
                ) as f:
                    f.write(self.model)
            except OSError:  # pragma: no cover — read-only fixture dirs
                pass
        return warming

    @property
    def warmup_coverage(self) -> float:
        if self.effective_ready_delay <= 0:
            return 1.0
        elapsed = time.monotonic() - self.warmup_started
        return min(elapsed / self.effective_ready_delay, 1.0)

    def account_prefix(self, prompt_text: str) -> int:
        """One generation's prefix-cache pass: count token-weighted hits
        against the simulated KV, then cache the prompt's chunks (LRU
        eviction at capacity). Returns matched chunk count."""
        hashes = kv_chunk_hashes(prompt_text)
        matched = 0
        for h in hashes:
            if h in self.kv_chunks:
                matched += 1
                self.kv_chunks.move_to_end(h)
            else:
                break  # chain hashes: first miss ends the match
        self.prefix_queries += len(hashes) * KV_CHUNK_TOKENS
        self.prefix_hits += matched * KV_CHUNK_TOKENS
        for h in hashes[matched:]:
            # A chunk past the first miss can still be cached (partial
            # LRU eviction left a hole): re-inserting it must not count
            # its token mass twice, or occupancy ratchets upward forever.
            if h not in self.kv_chunks:
                self.kv_tokens += KV_CHUNK_TOKENS
            self.kv_chunks[h] = KV_CHUNK_TOKENS
            self.kv_chunks.move_to_end(h)
        while self.kv_tokens > self.kv_capacity_tokens and self.kv_chunks:
            _, tokens = self.kv_chunks.popitem(last=False)
            self.kv_tokens -= tokens
        return matched

    @property
    def kv_occupancy(self) -> float:
        """Derived KV page occupancy: cached chunks + live decode state,
        floored by the /admin/fill_kv override."""
        live = self.kv_tokens + self.num_running * KV_RUNNING_TOKENS
        derived = min(live / self.kv_capacity_tokens, 1.0)
        return max(derived, min(max(self.kv_fill_floor, 0.0), 1.0))

    def fake_cost(self, prompt_tokens: int, n_tokens: int) -> dict:
        """Deterministic X-PST-Cost payload: the real engine's field set
        with values derived purely from token counts, so router/billing
        tests assert exact numbers."""
        prefill = round(prompt_tokens * 1e-4, 6)
        decode = round(n_tokens * 1e-3, 6)
        return {
            "prefill_device_s": prefill,
            "decode_device_s": decode,
            "device_s": round(prefill + decode, 6),
            "kv_page_s": round((prompt_tokens + n_tokens) * 0.01, 3),
            "queue_s": 0.0,
        }

    @staticmethod
    def _flight_cycle(device_s: float) -> dict:
        """The cycle's account of a record (obs/flight.py's fields after
        ``tokens``), a pure function of the dispatch's wall: all of it in
        ``wait``, polled every 0.3 ms, beside 0.5 ms of host work."""
        device_s = round(device_s, 6)
        return {
            "cycle_s": round(device_s + 0.0005, 6),
            "intake_s": 0.0001, "schedule_s": 0.0001,
            "batch_build_s": 0.0001, "launch_s": 0.0001,
            "wait_s": device_s, "postprocess_s": 0.0001,
            "offcpu_s": 0.0, "thread_cpu_s": 0.0005,
            "process_cpu_s": 0.0005, "gc_s": 0.0,
            "polls": int(device_s / 0.0003), "poll_gap_max_s": 0.0003,
            # the program the cycle fetched ran all of it, behind nothing
            "service_s": device_s, "queued_s": 0.0,
        }

    def record_flight(self, prompt_tokens: int, n_tokens: int) -> None:
        """Two deterministic ring records per generation (the prefill
        step and its decode burst), same field set as obs/flight.py."""
        base = {
            "ts": time.time(),
            "host_gap_s": 0.0005,
            "compiled": False,
            "waiting": self.num_waiting,
            "running": self.num_running,
            "swapped": 0,
            "kv_occupancy": round(self.kv_occupancy, 4),
            "preemptions": 0,
            "batch_tier_rows": 0,
        }
        self.flight_records.append({
            **base, "kind": "prefill",
            "bucket": f"b1xt{max(prompt_tokens, 1)}",
            "device_s": round(prompt_tokens * 1e-4, 6),
            "tokens": prompt_tokens,
            **self._flight_cycle(prompt_tokens * 1e-4),
        })
        self.flight_records.append({
            **base, "kind": "decode",
            "bucket": f"b{max(self.num_running, 1)}xn{max(n_tokens, 1)}",
            "device_s": round(n_tokens * 1e-3, 6),
            "tokens": n_tokens,
            **self._flight_cycle(n_tokens * 1e-3),
        })
        self.flight_total += 2
        if len(self.flight_records) > self.flight_capacity:
            del self.flight_records[: len(self.flight_records)
                                    - self.flight_capacity]

    def record_stall(self, stall_s: float, n_tokens: int) -> None:
        """One stalled decode cycle: an extra ring record whose wait is
        the injected stall, plus a retained tail_outlier snapshot naming
        the stalled bucket, queue state and cause (``device``: the wait
        polled at its pace) and the two stall counters — the same evidence
        the real recorder leaves for an unexplained p99 (obs/flight.py
        stall contract)."""
        bucket = f"b{max(self.num_running, 1)}xn{max(n_tokens, 1)}"
        baseline_s = max(n_tokens, 1) * 1e-3  # the unstalled decode cost
        row = {
            "ts": time.time(),
            "kind": "decode",
            "bucket": bucket,
            "device_s": round(stall_s, 6),
            "host_gap_s": 0.0005,
            "compiled": False,
            "waiting": self.num_waiting,
            "running": self.num_running,
            "swapped": 0,
            "kv_occupancy": round(self.kv_occupancy, 4),
            "preemptions": 0,
            "batch_tier_rows": 0,
            "tokens": n_tokens,
            **self._flight_cycle(stall_s),
        }
        excess_s = round(row["cycle_s"] - baseline_s, 6)
        self.stalls += 1
        self.stall_seconds += excess_s
        self.flight_records.append(row)
        self.flight_total += 1
        if len(self.flight_records) > self.flight_capacity:
            del self.flight_records[: len(self.flight_records)
                                    - self.flight_capacity]
        snap = {
            "reason": "tail_outlier",
            "ts": time.time(),
            "detail": {
                **row,
                "cause": "device",
                "phase": "wait",
                "excess_s": excess_s,
                "median_s": round(baseline_s, 6),
                "bar_s": round(baseline_s * 3.0, 6),
                "injected": "stall",
            },
            "total_steps": self.flight_total,
            "records": list(self.flight_records[-16:]),
        }
        self.flight_snapshots.append(snap)
        if len(self.flight_snapshots) > self.flight_snapshot_keep:
            del self.flight_snapshots[: len(self.flight_snapshots)
                                      - self.flight_snapshot_keep]
        d = self.flight_snapshot_dir
        if d:
            try:
                os.makedirs(d, exist_ok=True)
                self._snapshot_seq += 1
                name = (f"flight_{time.time_ns():020d}_"
                        f"{self._snapshot_seq:06d}_{snap['reason']}.json")
                tmp = os.path.join(d, name + ".tmp")
                with open(tmp, "w") as f:
                    json.dump(snap, f)
                os.replace(tmp, os.path.join(d, name))
            except OSError:
                pass

    def take_fault(self, tenant: Optional[str] = None) -> Optional[str]:
        """Consume one fault budget entry; returns the armed mode or None.

        With a tenant-scoped fault armed, only requests carrying that
        ``X-PST-Tenant`` value consume budget and fault — other tenants'
        traffic passes untouched (the flood-isolation chaos contract)."""
        if self.fail_mode is None or self.fail_count == 0:
            return None
        if self.fail_tenant is not None and tenant != self.fail_tenant:
            return None
        mode = self.fail_mode
        if self.fail_count > 0:
            self.fail_count -= 1
            if self.fail_count == 0:
                self.fail_mode = None
        self.num_faulted += 1
        return mode


class ChipSim:
    """Opt-in chip contention model (--chip-ms-per-ktok; bench's disagg
    phase), shaped like a continuously-batched serving chip:

    - a PREFILL is one **exclusive** slice — it stalls the running decode
      batch for its whole duration (the ITL hiccup / TTFT head-of-line
      interference fused engines suffer);
    - DECODE bursts are **shared** — all running streams burst
      concurrently (continuous batching), but no burst may start while a
      prefill runs or waits, and a prefill waits for in-flight bursts to
      drain (≤ one burst residual).

    Disaggregation removes exactly the cross-class interference this
    models: a prefill-pool chip never stalls on decode bursts, a
    decode-pool chip only pays tail-compute slices.
    """

    # Prefill slowdown per concurrently-decoding stream: a fused chip's
    # prefill competes with the running decode batch for compute/HBM
    # bandwidth — dedicated prefill chips escape exactly this factor.
    DECODE_DRAG = 0.35

    def __init__(self):
        self._cond = asyncio.Condition()
        self._prefill_active = False
        self._prefill_waiting = 0
        self._decode_bursts = 0
        self.decode_streams = 0

    def enter_decode(self) -> None:
        self.decode_streams += 1

    def exit_decode(self) -> None:
        self.decode_streams = max(self.decode_streams - 1, 0)

    def prefill_drag(self) -> float:
        """How much slower a prefill runs with the current decode batch
        resident on this chip."""
        return 1.0 + self.DECODE_DRAG * self.decode_streams

    async def acquire_prefill(self) -> None:
        async with self._cond:
            self._prefill_waiting += 1
            while self._prefill_active or self._decode_bursts:
                await self._cond.wait()
            self._prefill_waiting -= 1
            self._prefill_active = True

    async def release_prefill(self) -> None:
        async with self._cond:
            self._prefill_active = False
            self._cond.notify_all()

    async def prefill_slice(self, duration: float) -> None:
        await self.acquire_prefill()
        try:
            await asyncio.sleep(max(duration, 0.0) * self.prefill_drag())
        finally:
            await self.release_prefill()

    async def decode_burst(self, duration: float) -> None:
        async with self._cond:
            while self._prefill_active or self._prefill_waiting:
                await self._cond.wait()
            self._decode_bursts += 1
        try:
            await asyncio.sleep(max(duration, 0.0))
        finally:
            async with self._cond:
                self._decode_bursts -= 1
                self._cond.notify_all()


def _prompt_text(body: dict) -> str:
    """Flatten the request prompt (chat messages or completions prompt)
    into one text blob — the fake model's whole world view."""
    if "messages" in body:
        parts = []
        for m in body.get("messages") or []:
            c = m.get("content", "")
            if isinstance(c, str):
                parts.append(c)
        return "\n".join(parts)
    prompt = body.get("prompt", "")
    if isinstance(prompt, list):
        return "\n".join(str(p) for p in prompt)
    return str(prompt)


def _models_payload(state: FakeEngineState) -> dict:
    data = [
        {
            "id": state.model,
            "object": "model",
            "created": int(time.time()),
            "owned_by": "fake",
            "parent": None,
            "root": None,
        }
    ]
    for adapter in state.lora_adapters:
        data.append(
            {
                "id": adapter,
                "object": "model",
                "created": int(time.time()),
                "owned_by": "fake",
                "parent": state.model,
                "root": None,
            }
        )
    return {"object": "list", "data": data}


def create_fake_engine_app(
    model: str = "fake/model",
    speed: float = 500.0,
    ttft: float = 0.0,
    name: str = "",
    ready_delay: float = 0.0,
    warmup_cache_dir: Optional[str] = None,
    kv_capacity_tokens: int = 20000,
    kv_url: Optional[str] = None,
    kv_replication: int = 2,
) -> web.Application:
    state = FakeEngineState(model, speed, kv_capacity_tokens=kv_capacity_tokens,
                            kv_url=kv_url, kv_replication=kv_replication)
    # Instance identity for routing-distribution e2e assertions: surfaces in
    # the X-Served-By header of every generation response.
    state.name = name or f"fake-{uuid.uuid4().hex[:6]}"
    state.configure_warmup(ready_delay, warmup_cache_dir)
    app = web.Application()
    app["state"] = state
    # One simulated chip per engine for the opt-in contention model
    # (state.chip_ms_per_ktok; bench's disagg phase).
    app["chip"] = ChipSim()

    def _kv_session() -> aiohttp.ClientSession:
        sess = app.get("kv_session")
        if sess is None or sess.closed:
            sess = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10)
            )
            app["kv_session"] = sess
        return sess

    async def _close_kv_session(app_: web.Application) -> None:
        sess = app_.get("kv_session")
        if sess is not None and not sess.closed:
            await sess.close()

    app.on_cleanup.append(_close_kv_session)

    async def _kv_post_manifest(rid: str, payload: dict) -> bool:
        """Replicate a manifest append/marker to the request id's owner
        set; True when at least one owner acked (the survivors' view is
        what the consumer's owner-walk reads)."""
        ok = False
        for url in state.kv_owners(rid):
            try:
                async with _kv_session().post(
                    f"{url}/manifests/{rid}", json=payload
                ) as r:
                    r.raise_for_status()
                ok = True
            except (aiohttp.ClientError, OSError):
                continue
        return ok

    async def _kv_put_pages(
        pages: List[tuple], urls: Optional[List[str]] = None
    ) -> set:
        """Fan ``(hash, payload)`` pages to each page's ring owners (or an
        explicit url list); returns the hashes stored on >= 1 shard."""
        from ..kvserver.server import pack_blocks

        sess = _kv_session()
        by_owner: dict = {}
        for h, data in pages:
            for url in (urls if urls is not None else state.kv_owners(h)):
                by_owner.setdefault(url, []).append((h, data))
        stored: set = set()
        for url, group in by_owner.items():
            try:
                async with sess.post(
                    f"{url}/blocks", data=pack_blocks(group)
                ) as r:
                    r.raise_for_status()
                stored.update(h for h, _ in group)
            except (aiohttp.ClientError, OSError):
                continue
        return stored

    async def _kv_publish(rid: str, hashes: List[int], faulted: bool,
                          chunk_delay: Optional[float] = None) -> None:
        """Producer leg: publish deterministic pages + manifest appends in
        ``kv_publish_chunks`` batches with a delay between them — the
        simulated chunked prefill the decode side overlaps against. Pages
        fan to their R ring owners, so a single shard SIGKILLed
        mid-handoff leaves the transfer intact (the degradation matrix).
        A ``transfer`` fault (or a wholly-dead kvserver tier) publishes
        nothing, so the manifest never completes and the consumer times
        out into its fused fallback."""
        n = max(state.kv_publish_chunks, 1)
        per = max(-(-len(hashes) // n), 1)
        for i in range(0, len(hashes), per):
            chunk = hashes[i : i + per]
            if not faulted:
                stored = await _kv_put_pages(
                    [(h, f"page-{h}".encode()) for h in chunk]
                )
                ok = stored >= set(chunk)
                if ok:
                    ok = await _kv_post_manifest(rid, {"hashes": chunk})
                if ok:
                    state.kv_published_blocks += len(chunk)
                else:
                    faulted = True  # every owner of some page is dead
            await asyncio.sleep(
                state.kv_chunk_delay if chunk_delay is None else chunk_delay
            )
        if faulted:
            state.kv_transfer_fallbacks += 1
            return
        if not await _kv_post_manifest(
            rid, {"complete": True, "total_blocks": len(hashes)}
        ):
            state.kv_transfer_fallbacks += 1

    async def _kv_fetch_blocks(hashes: List[int]) -> int:
        """Batch-fetch blocks with per-hash ring-walk failover, integrity
        verification, quarantine-on-corrupt and read-repair — the fake
        twin of ShardedKVClient.get_blocks. Returns the number of VERIFIED
        blocks fetched; a corrupt copy is quarantined on its shard and the
        walk falls over to the next replica, never counting the bad copy."""
        from ..kvserver.server import unpack_blocks

        sess = _kv_session()
        groups: dict = {}
        for h in hashes:
            groups.setdefault(tuple(state.kv_walk(h)), []).append(h)
        fetched = 0
        repairs: dict = {}  # owner url -> [(hash, payload)]
        for walk, group in groups.items():
            owner_set = {h: set(state.kv_owners(h)) for h in group}
            remaining = list(group)
            missed: dict = {h: [] for h in group}
            for url in walk:
                if not remaining:
                    break
                got: dict = {}
                try:
                    async with sess.get(
                        f"{url}/blocks",
                        params={"hashes": ",".join(
                            str(h) for h in remaining
                        )},
                    ) as r:
                        if r.status == 200:
                            corrupt: List[int] = []
                            for h, data in unpack_blocks(
                                await r.read(), corrupt=corrupt
                            ):
                                got[h] = data
                            if corrupt:
                                state.kv_integrity_failures += len(corrupt)
                                try:
                                    async with sess.post(
                                        f"{url}/admin/quarantine",
                                        json={"hashes": corrupt},
                                    ):
                                        pass
                                except (aiohttp.ClientError, OSError):
                                    pass
                except (aiohttp.ClientError, OSError, ValueError):
                    pass
                still = []
                for h in remaining:
                    if h in got:
                        fetched += 1
                        for owner in missed[h]:
                            repairs.setdefault(owner, []).append(
                                (h, got[h])
                            )
                        continue
                    if url in owner_set[h]:
                        missed[h].append(url)
                    still.append(h)
                remaining = still
        for url, pages in repairs.items():
            stored = await _kv_put_pages(pages, urls=[url])
            state.kv_read_repairs += len(stored)
        return fetched

    async def _kv_prefetch(rid: str, faulted: bool) -> dict:
        """Consumer leg: follow the manifest (long-poll) and batch-fetch
        published blocks until the completion marker — the real handoff
        protocol. Timeout/fault → fused fallback (serve anyway)."""
        expire = time.monotonic() + state.kv_transfer_timeout
        have = 0
        fetched = 0
        complete = False
        while not faulted and time.monotonic() < expire:
            remaining = expire - time.monotonic()
            view = None
            sess = _kv_session()
            # Owner-walk manifest read: the first healthy owner carries
            # the long-poll, later owners get a quick check — a replica
            # that missed appends cannot stall the consumer.
            wait = round(min(remaining, 0.5), 3)
            for url in state.kv_owners(rid):
                try:
                    async with sess.get(
                        f"{url}/manifests/{rid}",
                        params={"wait_s": wait, "have": have},
                    ) as r:
                        state.manifest_fetches += 1
                        wait = 0
                        if r.status == 200:
                            view = await r.json()
                            break
                except (aiohttp.ClientError, OSError):
                    continue
            if view is None:
                await asyncio.sleep(0.02)
                continue
            try:
                new = (view.get("hashes") or [])[have:]
                if new:
                    fetched += await _kv_fetch_blocks(new)
                have = len(view.get("hashes") or [])
                if view.get("complete") and have >= int(
                    view.get("total_blocks") or 0
                ):
                    complete = True
                    break
            except (aiohttp.ClientError, OSError, ValueError):
                await asyncio.sleep(0.05)
        state.kv_prefetched_blocks += fetched
        if not complete:
            state.kv_transfer_fallbacks += 1
        return {"complete": complete, "blocks": fetched}

    async def list_models(request: web.Request) -> web.Response:
        return web.json_response(_models_payload(state))

    def _deadline_budget_s(request: web.Request) -> Optional[float]:
        """Remaining budget (seconds) from X-PST-Deadline-Ms, or None."""
        raw = request.headers.get("X-PST-Deadline-Ms")
        if raw is None:
            return None
        try:
            return float(raw) / 1000.0
        except ValueError:
            return None

    def _echo_trace_headers(request: web.Request) -> dict:
        """Echo the received trace headers back so e2e tests can assert
        propagation on every leg — including retries, hedges, and
        drain/shed rejections — without engine-side state."""
        out = {}
        tp = request.headers.get("traceparent")
        rid = request.headers.get("X-Request-Id")
        if tp is not None:
            out["X-Echo-Traceparent"] = tp
        if rid is not None:
            out["X-Echo-Request-Id"] = rid
        return out

    def _deadline_exceeded_response(request: web.Request) -> web.Response:
        return web.json_response(
            {"error": {"message": "deadline exceeded",
                       "type": "deadline_exceeded", "code": 504}},
            status=504,
            headers={"X-PST-Deadline-Exceeded": "1",
                     "X-Served-By": state.name,
                     **_echo_trace_headers(request)},
        )

    async def _generate(request: web.Request, is_chat: bool) -> web.StreamResponse:
        # Structured-log correlation (--log-format json): the router's
        # propagated trace/request ids land on this engine's log lines
        # and on its stage-histogram exemplars, so e2e legs can join
        # router logs, engine logs, exemplars and /debug/requests on one
        # trace id — same contract as the real engine server. The token
        # is released on EVERY exit path (shed/drain/warming/fault
        # included): aiohttp serves keep-alive requests sequentially in
        # one connection context, and a leaked binding would stamp the
        # NEXT request's log lines with this request's identity.
        parsed_tp = parse_traceparent(request.headers.get("traceparent"))
        trace_id = parsed_tp[0] if parsed_tp else None
        log_token = bind_log_context(
            request_id=request.headers.get("X-Request-Id"),
            trace_id=trace_id,
            tenant=request.headers.get("X-PST-Tenant"),
        )
        try:
            return await _generate_correlated(request, is_chat, trace_id)
        finally:
            unbind_log_context(log_token)

    async def _generate_correlated(
        request: web.Request, is_chat: bool, trace_id
    ) -> web.StreamResponse:
        body = await request.json()
        state.requests_seen.append(body)
        budget = _deadline_budget_s(request)
        state.deadlines_seen.append(request.headers.get("X-PST-Deadline-Ms"))
        state.traces_seen.append({
            "traceparent": request.headers.get("traceparent"),
            "request_id": request.headers.get("X-Request-Id"),
        })
        tenant = request.headers.get("X-PST-Tenant")
        state.tenants_seen.append({
            "tenant": tenant,
            "tenant_class": request.headers.get("X-PST-Tenant-Class"),
        })
        echo = _echo_trace_headers(request)
        t_admission = time.monotonic()
        if budget is not None and budget <= 0:
            # The real engine sheds already-expired work at admission; a
            # router honoring the contract never forwards such a request.
            return _deadline_exceeded_response(request)
        if state.sleeping:
            # Parity with the real engine's sleep gate: a slept engine
            # refuses generation outright. The tagged 503 lets the router
            # fail over (and fire a wake) without feeding the breaker.
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "service_unavailable", "code": 503}},
                status=503,
                headers={"X-PST-Sleeping": "1", **echo},
            )
        if state.draining:
            return web.json_response(
                {"error": {"message": "engine is draining",
                           "type": "service_unavailable", "code": 503}},
                status=503,
                headers={"X-PST-Draining": "1", **echo},
            )
        if state.warming:
            # Same tagged-503 contract as the real engine's warming gate:
            # the router marks the endpoint warming and fails over without
            # feeding the breaker.
            return web.json_response(
                {"error": {"message": "engine is warming up (precompiling)",
                           "type": "service_unavailable", "code": 503}},
                status=503,
                headers={"X-PST-Warming": "1", **echo},
            )
        fault = state.take_fault(tenant)
        if fault == "slow":
            delay = state.fail_delay
            if state.fail_jitter:
                delay += random.uniform(0.0, state.fail_jitter)
            if budget is not None and delay >= budget:
                # The injected latency blows the budget: honor the deadline
                # — sleep until it expires, then 504 (what a deadline-
                # shedding engine does when a sequence expires mid-decode).
                await asyncio.sleep(max(budget, 0.0))
                return _deadline_exceeded_response(request)
            await asyncio.sleep(delay)
            # ... then serve normally below (slow, not broken).
        if fault == "error":
            return web.json_response(
                {"error": {"message": "injected failure",
                           "type": "internal_error",
                           "code": state.fail_status}},
                status=state.fail_status,
                headers=echo,
            )
        if fault == "hang":
            # Hold the request open until the caller gives up (poll the
            # transport instead of one long sleep so server shutdown isn't
            # blocked behind a still-running handler).
            while request.transport is not None and not request.transport.is_closing():
                await asyncio.sleep(0.1)
            return web.Response(status=500)
        n_tokens = int(body.get("max_tokens") or state.max_tokens_default)
        stream = bool(body.get("stream", False))
        die_midstream = fault == "midstream"
        # Disagg KV handoff (docs/disagg.md): the router's two-leg flow
        # stamps kv_transfer_params; with a kvserver configured this fake
        # speaks the real manifest protocol. A `transfer` fault breaks
        # ONLY the handoff (fused fallback, no client-visible error).
        kv_params = body.get("kv_transfer_params")
        kv_params = kv_params if isinstance(kv_params, dict) else {}
        kv_rid = kv_params.get("request_id")
        kv_role = kv_params.get("role")
        transfer_fault = fault == "transfer"
        state.num_running += 1
        req_id = f"fake-{uuid.uuid4().hex[:12]}"
        token_interval = 1.0 / state.speed if state.speed > 0 else 0.0
        # Deterministic *continuation* semantics: the fake model's output
        # is "tokN tokN+1 ..." where N counts the tokNs already present in
        # the prompt — so a resume request carrying generated-so-far text
        # continues exactly where an unbroken run would have, like a
        # temperature-0 model continuing its own output.
        prompt_text = _prompt_text(body)
        state.account_prefix(prompt_text)
        tok_start = len(re.findall(r"tok\d+", prompt_text))
        # The fake "tokenizer": every generated tokN is one token (even
        # when a continuation glued it to the prompt tail without a
        # space), every other whitespace word is one token — so a
        # continuation request's prompt_tokens equals the original
        # prompt's plus the tokens already generated.
        prompt_tokens = max(
            tok_start + len(re.sub(r"tok\d+", " ", prompt_text).split()), 1
        )
        include_usage = bool(
            (body.get("stream_options") or {}).get("include_usage")
        )
        # Deterministic cost attribution + flight records (the real
        # engine's contract; docs/observability.md). The fake knows its
        # whole output upfront, so streams carry the header too.
        cost = state.fake_cost(prompt_tokens, n_tokens)
        cost_header = {"X-PST-Cost": json.dumps(cost, separators=(",", ":"))}
        state.record_flight(prompt_tokens, n_tokens)
        if fault == "stall":
            # One-shot N-ms stall on this generation's decode step (the
            # BENCH_r05 signature, inducible on CPU): the request serves
            # normally but pays fail_delay seconds first, and the flight
            # ring retains a deterministic tail_outlier snapshot naming
            # the stalled bucket and queue depths.
            await asyncio.sleep(max(state.fail_delay, 0.0))
            state.record_stall(state.fail_delay, n_tokens)
        created = int(time.time())
        logger.info(
            "generation: model=%s stream=%s tokens=%s",
            body.get("model"), bool(body.get("stream")),
            body.get("max_tokens"),
        )
        chip = request.app.get("chip")
        chip_on = state.chip_ms_per_ktok > 0 and chip is not None
        decode_entered = False
        try:
            # Mirror the real engine's stage decomposition so mixed-workload
            # e2e tests see engine-side pst_stage_duration_seconds labels
            # (with the propagated trace id as the bucket exemplar).
            observe_stage("engine", "engine_admission",
                          time.monotonic() - t_admission,
                          trace_id=trace_id)
            prefetch_complete = False
            if kv_rid and state.kv_url and kv_role == "consumer":
                # Prefetch BEFORE the chip: following the manifest is
                # DCN work, not compute — it overlaps the remote prefill.
                t_fetch = time.monotonic()
                fetch = await _kv_prefetch(str(kv_rid), transfer_fault)
                prefetch_complete = fetch["complete"]
                observe_stage("engine", "kv_prefetch",
                              time.monotonic() - t_fetch, trace_id=trace_id)
            t_prefill = time.monotonic()
            if ttft:
                await asyncio.sleep(ttft)
            prefill_s = 0.0
            if chip_on:
                prefill_s = (prompt_tokens / 1000.0) * (
                    state.chip_ms_per_ktok / 1000.0
                )
                if kv_role == "consumer" and prefetch_complete:
                    prefill_s *= 0.1  # prefix arrived over the wire
            if kv_rid and state.kv_url and kv_role == "producer":
                # The simulated chunked prefill IS the publish loop: each
                # chunk's blocks land on the store before the next chunk
                # "computes", so a concurrently dispatched decode leg
                # observes genuine transfer/prefill overlap. Under the
                # chip model the prefill slice is exclusive and the
                # per-chunk pacing IS the slice (publishing adds no wall
                # beyond the compute it rides).
                if chip_on:
                    # The publisher runs OFF the step thread in the real
                    # engine: the chunk-paced publish overlaps the
                    # exclusive prefill slice instead of inflating it
                    # with DCN round trips.
                    n_chunks = max(state.kv_publish_chunks, 1)
                    pub = asyncio.ensure_future(_kv_publish(
                        str(kv_rid), kv_chunk_hashes(prompt_text),
                        transfer_fault,
                        chunk_delay=prefill_s / n_chunks,
                    ))
                    try:
                        await chip.prefill_slice(prefill_s)
                    finally:
                        await pub
                else:
                    await _kv_publish(
                        str(kv_rid), kv_chunk_hashes(prompt_text),
                        transfer_fault,
                    )
            elif chip_on and prefill_s > 0:
                await chip.prefill_slice(prefill_s)
            observe_stage("engine", "prefill", time.monotonic() - t_prefill,
                          trace_id=trace_id)
            t_decode = time.monotonic()
            decode_count = 0
            if chip_on and n_tokens > 1:
                # This request's decode stream joins the chip's resident
                # batch: every prefill pays the drag while it lives.
                chip.enter_decode()
                decode_entered = True

            async def decode_pace():
                """One token of decode. Under the chip model tokens are
                produced in bursts of 8 holding the chip exclusively —
                the multi-step decode burst that makes an arriving
                prefill wait, i.e. the interference disagg removes."""
                nonlocal decode_count
                if chip_on:
                    if decode_count % 8 == 0:
                        burst = min(8, n_tokens - decode_count)
                        await chip.decode_burst(
                            burst * (token_interval or 0.0005)
                        )
                    decode_count += 1
                elif token_interval:
                    await asyncio.sleep(token_interval)
            if stream:
                resp = web.StreamResponse(status=200)
                resp.headers["Content-Type"] = "text/event-stream"
                resp.headers["X-Served-By"] = state.name
                resp.headers.update(cost_header)
                for k, v in echo.items():
                    resp.headers[k] = v
                await resp.prepare(request)
                for i in range(n_tokens):
                    if die_midstream and i >= state.fail_after_chunks:
                        # Drop the connection at the exact chunk boundary
                        # (0 = before any delta reaches the wire).
                        request.transport.close()
                        return resp
                    final = i == n_tokens - 1
                    finish = "length" if final else None
                    if is_chat:
                        chunk = {
                            "id": req_id,
                            "object": "chat.completion.chunk",
                            "created": created,
                            "model": state.model,
                            "choices": [
                                {
                                    "index": 0,
                                    "delta": {"content": f"tok{tok_start + i} "},
                                    "finish_reason": finish,
                                }
                            ],
                        }
                    else:
                        chunk = {
                            "id": req_id,
                            "object": "text_completion",
                            "created": created,
                            "model": state.model,
                            "choices": [
                                {"index": 0, "text": f"tok{tok_start + i} ",
                                 "finish_reason": finish}
                            ],
                        }
                    # No pst_cost in the streamed usage chunk: the
                    # router's stream journal merges cross-leg usage down
                    # to the three OpenAI fields, so a resumed stream
                    # must byte-match an unfaulted one — the fake's
                    # streaming cost surface is the X-PST-Cost header
                    # (deterministic, so it CAN ride the 200 headers).
                    if final and include_usage:
                        chunk["usage"] = {
                            "prompt_tokens": prompt_tokens,
                            "completion_tokens": n_tokens,
                            "total_tokens": prompt_tokens + n_tokens,
                        }
                    await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
                    await decode_pace()
                if die_midstream:
                    # fail_after_chunks >= max_tokens: death after the last
                    # delta but before the terminal [DONE].
                    request.transport.close()
                    return resp
                await resp.write(b"data: [DONE]\n\n")
                observe_stage("engine", "decode",
                              time.monotonic() - t_decode,
                              trace_id=trace_id)
                await resp.write_eof()
                return resp
            else:
                if chip_on:
                    for _ in range(n_tokens):
                        await decode_pace()
                elif token_interval:
                    await asyncio.sleep(token_interval * n_tokens)
                text = " ".join(f"tok{tok_start + i}" for i in range(n_tokens))
                usage = {
                    "prompt_tokens": prompt_tokens,
                    "completion_tokens": n_tokens,
                    "total_tokens": prompt_tokens + n_tokens,
                    "pst_cost": cost,
                }
                if is_chat:
                    payload = {
                        "id": req_id,
                        "object": "chat.completion",
                        "created": created,
                        "model": state.model,
                        "choices": [
                            {
                                "index": 0,
                                "message": {"role": "assistant", "content": text},
                                "finish_reason": "length",
                            }
                        ],
                        "usage": usage,
                    }
                else:
                    payload = {
                        "id": req_id,
                        "object": "text_completion",
                        "created": created,
                        "model": state.model,
                        "choices": [
                            {"index": 0, "text": text, "finish_reason": "length"}
                        ],
                        "usage": usage,
                    }
                observe_stage("engine", "decode",
                              time.monotonic() - t_decode,
                              trace_id=trace_id)
                return web.json_response(
                    payload,
                    headers={"X-Served-By": state.name, **cost_header, **echo},
                )
        finally:
            state.num_running -= 1
            if decode_entered:
                chip.exit_decode()

    async def chat(request: web.Request) -> web.StreamResponse:
        return await _generate(request, is_chat=True)

    async def completions(request: web.Request) -> web.StreamResponse:
        return await _generate(request, is_chat=False)

    async def metrics(request: web.Request) -> web.Response:
        hit_rate = state.prefix_hits / state.prefix_queries if state.prefix_queries else 0.0
        text = "\n".join(
            [
                "# TYPE vllm:num_requests_running gauge",
                f"vllm:num_requests_running {state.num_running}",
                "# TYPE vllm:num_requests_waiting gauge",
                f"vllm:num_requests_waiting {state.num_waiting}",
                "# TYPE vllm:gpu_prefix_cache_hit_rate gauge",
                f"vllm:gpu_prefix_cache_hit_rate {hit_rate}",
                "# TYPE vllm:gpu_prefix_cache_hits_total counter",
                f"vllm:gpu_prefix_cache_hits_total {state.prefix_hits}",
                "# TYPE vllm:gpu_prefix_cache_queries_total counter",
                f"vllm:gpu_prefix_cache_queries_total {state.prefix_queries}",
                "# TYPE vllm:gpu_cache_usage_perc gauge",
                f"vllm:gpu_cache_usage_perc {state.kv_occupancy:.4f}",
                # Engine telemetry (docs/observability.md "Engine
                # telemetry"): deterministic values so router-side SLO /
                # scraper e2e tests run hermetically against the fake.
                "# TYPE pst_engine_compile counter",
                'pst_engine_compile_total{kind="prefill",shape_bucket="b1xt128"} 3',
                'pst_engine_compile_total{kind="decode",shape_bucket="b4"} 2',
                "# TYPE pst_engine_compile_seconds histogram",
                'pst_engine_compile_seconds_bucket{kind="prefill",le="+Inf"} 3',
                'pst_engine_compile_seconds_sum{kind="prefill"} 4.5',
                'pst_engine_compile_seconds_count{kind="prefill"} 3',
                "# TYPE pst_engine_step_duration_seconds histogram",
                'pst_engine_step_duration_seconds_bucket{kind="decode",batch_bucket="b4",le="+Inf"} 10',
                'pst_engine_step_duration_seconds_sum{kind="decode",batch_bucket="b4"} 0.5',
                'pst_engine_step_duration_seconds_count{kind="decode",batch_bucket="b4"} 10',
                # The device's own time of the same ten steps (the engine's
                # completion clock), beside the host's wall above.
                "# TYPE pst_engine_device_step_seconds histogram",
                'pst_engine_device_step_seconds_bucket{kind="decode",le="0.05"} 9',
                'pst_engine_device_step_seconds_bucket{kind="decode",le="+Inf"} 9',
                'pst_engine_device_step_seconds_sum{kind="decode"} 0.45',
                'pst_engine_device_step_seconds_count{kind="decode"} 9',
                "# TYPE pst_engine_device_service_seconds counter",
                'pst_engine_device_service_seconds_total{kind="decode",seen="poll"} 0.45',
                'pst_engine_device_service_seconds_total{kind="decode",seen="late"} 0.05',
                "# TYPE pst_engine_device_idle_seconds counter",
                'pst_engine_device_idle_seconds_total{state="host"} 0.02',
                'pst_engine_device_idle_seconds_total{state="no_work"} 1.5',
                "# TYPE pst_engine_loop_seconds counter",
                'pst_engine_loop_seconds_total{state="decode"} 0.52',
                'pst_engine_loop_seconds_total{state="no_work"} 1.5',
                "# TYPE pst_engine_loop_cycles counter",
                'pst_engine_loop_cycles_total{state="decode"} 10',
                'pst_engine_loop_cycles_total{state="no_work"} 30',
                "# TYPE pst_engine_batch_fill_ratio histogram",
                'pst_engine_batch_fill_ratio_bucket{kind="decode",le="+Inf"} 10',
                'pst_engine_batch_fill_ratio_sum{kind="decode"} 7.5',
                'pst_engine_batch_fill_ratio_count{kind="decode"} 10',
                "# TYPE pst_engine_tokens_per_second gauge",
                'pst_engine_tokens_per_second{kind="decode"} 1234.0',
                "# TYPE pst_engine_kv_page_occupancy gauge",
                f"pst_engine_kv_page_occupancy {state.kv_occupancy:.4f}",
                "# TYPE pst_engine_kv_page_high_watermark gauge",
                "pst_engine_kv_page_high_watermark 0.55",
                "# TYPE pst_engine_host_gap_seconds histogram",
                'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="0.001"} 5',
                'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="0.005"} 8',
                'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="+Inf"} 10',
                'pst_engine_host_gap_seconds_sum{batch_bucket="b4"} 0.02',
                'pst_engine_host_gap_seconds_count{batch_bucket="b4"} 10',
                # What held a step off (docs/observability.md "Flight
                # recorder"): every cause from the start, the injected
                # stalls under `device`.
                "# TYPE pst_engine_step_offcpu_seconds histogram",
                'pst_engine_step_offcpu_seconds_bucket{kind="decode",le="0.001"} 9',
                'pst_engine_step_offcpu_seconds_bucket{kind="decode",le="+Inf"} 10',
                'pst_engine_step_offcpu_seconds_sum{kind="decode"} 0.004',
                'pst_engine_step_offcpu_seconds_count{kind="decode"} 10',
                "# TYPE pst_engine_stalls counter",
                *(f'pst_engine_stalls_total{{cause="{c}"}} '
                  f'{state.stalls if c == "device" else 0}'
                  for c in STALL_CAUSES),
                "# TYPE pst_engine_stall_seconds counter",
                *(f'pst_engine_stall_seconds_total{{cause="{c}"}} '
                  f'{state.stall_seconds if c == "device" else 0.0:.6f}'
                  for c in STALL_CAUSES),
                "# TYPE pst_engine_gc_pause_seconds counter",
                "pst_engine_gc_pause_seconds_total 0.5",
                "# TYPE pst_engine_preemptions counter",
                "pst_engine_preemptions_total 1",
                "# TYPE pst_engine_swap_out counter",
                "pst_engine_swap_out_total 2",
                "# TYPE pst_engine_swap_in counter",
                "pst_engine_swap_in_total 2",
                "# TYPE pst_engine_start_time_seconds gauge",
                "pst_engine_start_time_seconds 1700000000.0",
                "# TYPE pst_engine_startup_seconds gauge",
                'pst_engine_startup_seconds{phase="load"} 120.0',
                'pst_engine_startup_seconds{phase="shard"} 15.0',
                'pst_engine_startup_seconds{phase="warmup"} 5.0',
                # Simulated precompile warmup (docs/engine.md "Warmup &
                # precompilation"): phase time tracks the effective ready
                # delay (warm restarts report a strictly smaller value),
                # coverage climbs 0→1 during the delay, and the cache
                # counters are all-misses cold / all-hits warm.
                'pst_engine_startup_seconds{phase="precompile"} '
                f"{state.effective_ready_delay:.3f}",
                "# TYPE pst_engine_warmup_coverage gauge",
                f"pst_engine_warmup_coverage {state.warmup_coverage:.4f}",
                "# TYPE pst_engine_warmup_buckets gauge",
                'pst_engine_warmup_buckets{state="total"} '
                f"{FAKE_WARMUP_BUCKETS}",
                'pst_engine_warmup_buckets{state="compiled"} '
                f"{int(round(state.warmup_coverage * FAKE_WARMUP_BUCKETS))}",
                "# TYPE pst_engine_compile_cache_hits counter",
                "pst_engine_compile_cache_hits_total "
                f"{FAKE_WARMUP_BUCKETS if state.warm_start else 0}",
                "# TYPE pst_engine_compile_cache_misses counter",
                "pst_engine_compile_cache_misses_total "
                f"{0 if state.warm_start else FAKE_WARMUP_BUCKETS}",
                # Streamed disagg handoff (docs/disagg.md) — same pst:
                # names as the real engine server.
                "# TYPE pst:kv_published_blocks counter",
                f"pst:kv_published_blocks_total {state.kv_published_blocks}",
                "# TYPE pst:kv_prefetched_blocks counter",
                f"pst:kv_prefetched_blocks_total {state.kv_prefetched_blocks}",
                "# TYPE pst:kv_transfer_fallbacks counter",
                "pst:kv_transfer_fallbacks_total "
                f"{state.kv_transfer_fallbacks}",
                # Replicated remote tier (docs/kvserver.md) — underscore
                # names, same as the real engines' shared obs registry.
                "# TYPE pst_kv_integrity_failures counter",
                'pst_kv_integrity_failures_total{source="prefetch"} '
                f"{state.kv_integrity_failures}",
                "# TYPE pst_kv_read_repairs counter",
                f"pst_kv_read_repairs_total {state.kv_read_repairs}",
                "",
            ]
        )
        # Same contract as the real engine: pst_stage_duration_seconds
        # rides the shared observability registry.
        text += render_obs_metrics().decode()
        return web.Response(text=text, content_type="text/plain")

    async def debug_profile(request: web.Request) -> web.Response:
        """Same surface as the real engine's POST /debug/profile, always
        the graceful CPU no-op (a fake engine has no device timeline)."""
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:  # noqa: BLE001
                body = {}
        if not isinstance(body, dict):  # e.g. a bare JSON list
            body = {}
        try:
            duration_ms = float(
                body.get("duration_ms")
                or request.query.get("duration_ms", 1000)
            )
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "duration_ms must be a number",
                           "type": "invalid_request_error", "code": 400}},
                status=400,
            )
        return web.json_response({
            "status": "skipped",
            "reason": "no accelerator backend (fake engine) — nothing to "
                      "profile",
            "duration_ms": duration_ms,
        })

    async def debug_state(request: web.Request) -> web.Response:
        """Deterministic engine introspection (docs/observability.md
        "Fleet debugging"): the same KV/tenant/compile numbers the
        /metrics surface exports, as one JSON object — what /debug/fleet
        shows for this engine, straight from the source, so tests can
        cross-validate the gossip-merged snapshot against engine truth."""
        hit_rate = (
            state.prefix_hits / state.prefix_queries
            if state.prefix_queries else 0.0
        )
        return web.json_response({
            "name": state.name,
            "model": state.model,
            # Same conjuncts as the real engine's readiness: sleeping is
            # not ready (a contract test written against the fake must
            # hold against the real engine too).
            "ready": not (state.warming or state.draining or state.sleeping
                          or state.fail_mode == "error"),
            "draining": state.draining,
            "warming": state.warming,
            "sleeping": state.sleeping,
            "sleep_level": state.sleep_level,
            "in_flight": state.num_running,
            "kv_occupancy": round(state.kv_occupancy, 4),
            "kv_capacity_tokens": state.kv_capacity_tokens,
            "cached_tokens": state.kv_tokens,
            "kv_published_blocks": state.kv_published_blocks,
            "kv_prefetched_blocks": state.kv_prefetched_blocks,
            "kv_transfer_fallbacks": state.kv_transfer_fallbacks,
            "kv_read_repairs": state.kv_read_repairs,
            "kv_integrity_failures": state.kv_integrity_failures,
            "kv_shards": len(state.kv_urls),
            "kv_replication": state.kv_replication,
            "manifest_fetches": state.manifest_fetches,
            "prefix_hit_rate": round(hit_rate, 4),
            # Matches the deterministic pst_engine_compile_total samples
            # in /metrics (3 prefill + 2 decode).
            "compiles_total": 5,
            "flight": {
                "capacity": state.flight_capacity,
                "total_steps": state.flight_total,
                "resident": len(state.flight_records),
                "snapshots": 0,
            },
            "tenants_seen": state.tenants_seen[-64:],
            "requests_seen": len(state.requests_seen),
        })

    async def debug_flight(request: web.Request) -> web.Response:
        """Deterministic flight-recorder ring (the real engine's
        GET /debug/flight shape): two records per generation served, so
        router-side capacity/cost tests assert exact contents without a
        TPU. Supports the same ``?n=`` / ``?window_s=`` filters."""
        records = list(state.flight_records)
        try:
            if "window_s" in request.query:
                cutoff = time.time() - float(request.query["window_s"])
                records = [r for r in records if r["ts"] >= cutoff]
            if "n" in request.query:
                n = int(request.query["n"])
                if n > 0:
                    records = records[-n:]
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "n and window_s must be numbers",
                           "type": "invalid_request_error", "code": 400}},
                status=400,
            )
        return web.json_response({
            "capacity": state.flight_capacity,
            "total_steps": state.flight_total,
            "resident": len(state.flight_records),
            "fields": list(FLIGHT_FIELDS),
            "records": records,
            "snapshot_log": list(state.flight_snapshots),
            **(
                {"restored_snapshots": list(state.restored_snapshots),
                 "snapshot_dir": state.flight_snapshot_dir}
                if request.query.get("snapshots") in ("1", "true") else {}
            ),
        })

    async def health(request: web.Request) -> web.Response:
        if state.fail_mode == "error":
            return web.json_response({"status": "failing"}, status=500)
        status = (
            "draining" if state.draining
            else "warming" if state.warming
            else "ok"
        )
        return web.json_response({"status": status})

    async def ready(request: web.Request) -> web.Response:
        """Same contract as the real engine's /ready: 200 once the
        (simulated) precompile pass finished, 503 + reason otherwise."""
        warmup = {
            "mode": "full" if state.ready_delay else "off",
            "buckets_total": FAKE_WARMUP_BUCKETS,
            "buckets_compiled": int(
                round(state.warmup_coverage * FAKE_WARMUP_BUCKETS)
            ),
            "coverage": round(state.warmup_coverage, 4),
            "seconds": round(state.effective_ready_delay, 3),
            "warm_start": state.warm_start,
        }
        if state.fail_mode == "error":
            reason = "unhealthy"
        elif state.sleeping:
            reason = "sleeping"
        elif state.warming:
            reason = "warming"
        elif state.draining:
            reason = "draining"
        else:
            return web.json_response({"ready": True, "warmup": warmup})
        return web.json_response(
            {"ready": False, "reason": reason, "warmup": warmup}, status=503
        )

    async def admin_warmup(request: web.Request) -> web.Response:
        """Re-enter (or reconfigure) the simulated warmup: {"ready_delay":
        seconds, "cache_dir": path|null, "reset_cache": bool}. Lets
        discovery/routing tests flip an engine to warming mid-run without
        restarting the app."""
        body = await request.json() if request.can_read_body else {}
        cache_dir = body.get("cache_dir", state.warmup_cache_dir)
        if body.get("reset_cache") and cache_dir:
            try:
                os.remove(os.path.join(cache_dir, "warm"))
            except OSError:
                pass
        state.configure_warmup(
            float(body.get("ready_delay", state.ready_delay)), cache_dir
        )
        return web.json_response({
            "status": "warming" if state.warming else "ready",
            "warm_start": state.warm_start,
            "effective_ready_delay": state.effective_ready_delay,
        })

    async def is_sleeping(request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": state.sleeping})

    async def admin_fail(request: web.Request) -> web.Response:
        """Arm fault injection: {"mode": "error"|"hang"|"midstream"|"slow",
        "status": 500, "count": -1, "delay": 0.5, "jitter": 0,
        "fail_after_chunks": 3, "tenant": null}. ``slow`` injects
        ``delay`` (+ uniform jitter up to ``jitter``) seconds of latency
        per generation, honoring a propagated deadline with 504.
        ``midstream`` drops the connection after exactly
        ``fail_after_chunks`` streamed delta chunks (0 = before any
        delta; >= max_tokens = after the last delta but before
        ``[DONE]``) — deterministic chunk boundaries for stream
        resumption tests. ``tenant`` scopes the fault to requests whose
        ``X-PST-Tenant`` equals it (isolation chaos legs fault one
        tenant's traffic while the victim's flows untouched). ``stall``
        one-shots a ``delay``-second pause on the next decode step and
        records a deterministic flight snapshot naming the stalled
        bucket + queue state — the BENCH_r05 tail signature on CPU
        (``count`` defaults to 1 for stall: one outlier, not a slow
        engine)."""
        body = await request.json() if request.can_read_body else {}
        mode = body.get("mode", "error")
        if mode not in ("error", "hang", "midstream", "slow", "transfer", "stall"):
            return web.json_response({"error": f"unknown mode {mode!r}"}, status=400)
        state.fail_mode = mode
        state.fail_status = int(body.get("status", 500))
        state.fail_count = int(body.get("count", 1 if mode == "stall" else -1))
        state.fail_delay = float(body.get("delay", 0.5))
        state.fail_jitter = float(body.get("jitter", 0.0))
        state.fail_after_chunks = int(body.get("fail_after_chunks", 3))
        tenant = body.get("tenant")
        state.fail_tenant = str(tenant) if tenant is not None else None
        return web.json_response(
            {"status": "armed", "mode": mode, "tenant": state.fail_tenant}
        )

    async def admin_heal(request: web.Request) -> web.Response:
        state.fail_mode = None
        state.fail_count = -1
        state.fail_tenant = None
        return web.json_response({"status": "healed", "faulted": state.num_faulted})

    async def admin_fill_kv(request: web.Request) -> web.Response:
        """Pin the reported KV occupancy for headroom-spill tests:
        {"occupancy": 0.9} floors the derived occupancy at 0.9;
        {"clear": true} drops the floor AND the simulated cache;
        {"capacity_tokens": N} resizes the simulated KV."""
        body = await request.json() if request.can_read_body else {}
        if not isinstance(body, dict):
            body = {}
        if body.get("clear"):
            state.kv_fill_floor = 0.0
            state.kv_chunks.clear()
            state.kv_tokens = 0
        if "capacity_tokens" in body:
            try:
                state.kv_capacity_tokens = max(int(body["capacity_tokens"]), 1)
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": "capacity_tokens must be an int"}, status=400
                )
        if "occupancy" in body:
            try:
                state.kv_fill_floor = float(body["occupancy"])
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": "occupancy must be a number"}, status=400
                )
        return web.json_response({
            "occupancy": state.kv_occupancy,
            "fill_floor": state.kv_fill_floor,
            "cached_tokens": state.kv_tokens,
            "capacity_tokens": state.kv_capacity_tokens,
        })

    async def drain(request: web.Request) -> web.Response:
        state.draining = True
        if request.query.get("wait"):
            deadline = time.time() + float(request.query.get("timeout", "30"))
            while time.time() < deadline and state.num_running > 0:
                await asyncio.sleep(0.05)
        return web.json_response(
            {"status": "draining", "in_flight": state.num_running}
        )

    async def undrain(request: web.Request) -> web.Response:
        state.draining = False
        return web.json_response(
            {"status": "accepting", "in_flight": state.num_running}
        )

    async def is_draining(request: web.Request) -> web.Response:
        return web.json_response(
            {"is_draining": state.draining, "in_flight": state.num_running}
        )

    async def sleep(request: web.Request) -> web.Response:
        level = request.query.get("level", "1")
        state.sleeping = True
        state.sleep_level = level
        return web.json_response({"status": "sleeping", "level": level})

    async def wake_up(request: web.Request) -> web.Response:
        was_sleeping = state.sleeping
        state.sleeping = False
        state.sleep_level = None
        if was_sleeping:
            # Wake re-enters the simulated warmup exactly like a restart:
            # ``--ready-delay`` governs the wake time, and a warm compile
            # cache (marker file present) shrinks it to the warm-restart
            # fraction — zero fresh compiles, scale-to-zero's
            # wake->first-token bound becomes CPU-measurable.
            state.configure_warmup(state.ready_delay, state.warmup_cache_dir)
        return web.json_response({
            "status": "awake",
            "warming": state.warming,
            "effective_ready_delay": round(state.effective_ready_delay, 3),
        })

    async def load_lora(request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        if name and name not in state.lora_adapters:
            state.lora_adapters.append(name)
        return web.json_response({"status": "ok"})

    async def unload_lora(request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        if name in state.lora_adapters:
            state.lora_adapters.remove(name)
        return web.json_response({"status": "ok"})

    async def tokenize(request: web.Request) -> web.Response:
        body = await request.json()
        text = body.get("prompt") or ""
        tokens = list(text.encode())
        return web.json_response({"tokens": tokens, "count": len(tokens)})

    async def embeddings(request: web.Request) -> web.Response:
        """Deterministic 64-dim embeddings (the real engine serves model
        embeddings via its encode path; same text → same vector is what
        router-side consumers like the semantic cache need from a fake)."""
        import xxhash

        body = await request.json()
        inputs = body.get("input")
        if isinstance(inputs, str):
            inputs = [inputs]
        data = []
        for i, text in enumerate(inputs or []):
            raw = [
                (xxhash.xxh32_intdigest(f"{text}\x00{j}") % 2001) / 1000.0 - 1.0
                for j in range(64)
            ]
            norm = sum(v * v for v in raw) ** 0.5 or 1.0
            data.append({
                "object": "embedding",
                "index": i,
                "embedding": [v / norm for v in raw],
            })
        return web.json_response({
            "object": "list",
            "data": data,
            "model": body.get("model", state.model),
            "usage": {"prompt_tokens": 0, "total_tokens": 0},
        })

    app.router.add_get("/v1/models", list_models)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_post("/v1/chat/completions", chat)
    app.router.add_post("/v1/completions", completions)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/state", debug_state)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_get("/health", health)
    app.router.add_get("/ready", ready)
    app.router.add_get("/is_sleeping", is_sleeping)
    app.router.add_post("/sleep", sleep)
    app.router.add_post("/wake_up", wake_up)
    app.router.add_post("/admin/fail", admin_fail)
    app.router.add_post("/admin/heal", admin_heal)
    app.router.add_post("/admin/fill_kv", admin_fill_kv)
    app.router.add_post("/admin/warmup", admin_warmup)
    app.router.add_post("/drain", drain)
    app.router.add_post("/undrain", undrain)
    app.router.add_get("/is_draining", is_draining)
    app.router.add_post("/v1/load_lora_adapter", load_lora)
    app.router.add_post("/v1/unload_lora_adapter", unload_lora)
    app.router.add_post("/tokenize", tokenize)
    return app


def main(argv: Optional[list] = None) -> None:
    p = argparse.ArgumentParser(description="fake TPU serving engine")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9101)
    p.add_argument("--model", default="fake/model")
    p.add_argument("--speed", type=float, default=500.0, help="tokens/sec")
    p.add_argument("--ttft", type=float, default=0.0, help="artificial TTFT (s)")
    p.add_argument("--name", default="", help="instance id (X-Served-By header)")
    p.add_argument("--ready-delay", type=float, default=0.0,
                   help="simulated warmup: /ready reports warming for this "
                        "many seconds after start")
    p.add_argument("--warmup-cache-dir", default=None,
                   help="simulated persistent compile cache: a marker left "
                        "by a previous instance makes this start warm "
                        "(shorter ready delay, all cache hits)")
    p.add_argument("--chip-ms-per-ktok", type=float, default=0.0,
                   help="opt-in chip queueing model: one FIFO chip per "
                        "engine; a prefill is one exclusive slice of this "
                        "many ms per 1000 prompt tokens, each decode "
                        "token a small slice — models the prefill/decode "
                        "head-of-line interference disagg removes "
                        "(bench disagg phase; 0 = off)")
    p.add_argument("--kv-url", default=None,
                   help="remote KV block store (kvserver) base URL: "
                        "enables the disagg handoff protocol — producer "
                        "legs publish deterministic block manifests per "
                        "simulated prefill chunk, consumer legs follow "
                        "them and batch-fetch before decoding; a comma-"
                        "separated list enables the sharded ring client "
                        "(placement, replication, read-repair)")
    p.add_argument("--kv-replication", type=int, default=2,
                   help="replicas per block/manifest on the kvserver "
                        "ring (clamped to the shard count)")
    p.add_argument("--kv-capacity-tokens", type=int, default=20000,
                   help="simulated KV capacity: occupancy and prefix-hit "
                        "eviction derive from it (small values make "
                        "cache-pressure effects visible in tests)")
    p.add_argument("--flight-snapshot-dir", default=None,
                   help="persist flight snapshots (stall outliers) as "
                        "JSON files here, same naming contract as the "
                        "real engine's --flight-snapshot-dir — the "
                        "post-mortem path: snapshots survive "
                        "SIGKILL; any already in the dir are "
                        "loaded back and served via "
                        "/debug/flight?snapshots=1")
    p.add_argument("--log-format", choices=["text", "json"], default="text",
                   help="'json' emits structured log lines enriched with "
                        "the propagated trace/request/tenant ids (same "
                        "contract as the real engine server)")
    args = p.parse_args(argv)
    configure_logging(
        args.log_format, component="engine",
        engine_id=args.name or f"fake:{args.port}",
    )
    app = create_fake_engine_app(
        args.model, args.speed, args.ttft, args.name,
        ready_delay=args.ready_delay, warmup_cache_dir=args.warmup_cache_dir,
        kv_capacity_tokens=args.kv_capacity_tokens,
        kv_url=args.kv_url,
        kv_replication=args.kv_replication,
    )
    app["state"].chip_ms_per_ktok = max(args.chip_ms_per_ktok, 0.0)
    if args.flight_snapshot_dir:
        from ..obs.flight import load_snapshot_dir

        app["state"].flight_snapshot_dir = args.flight_snapshot_dir
        app["state"].restored_snapshots = load_snapshot_dir(
            args.flight_snapshot_dir
        )
    web.run_app(app, host=args.host, port=args.port, access_log=None)


if __name__ == "__main__":
    main()
