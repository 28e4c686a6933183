"""Async façade over :class:`LLMEngine` for the aiohttp server.

The device step loop runs on a dedicated thread (a jitted TPU step blocks);
request submission and streaming consumption happen on the asyncio loop.
Outputs cross threads via ``loop.call_soon_threadsafe`` into per-request
queues — the same engine-loop/frontend split vLLM's AsyncLLMEngine gives the
reference stack, minus multiprocessing.

Sleep/wake (reference `/sleep`, `/wake_up`, tutorial 19): sleeping pauses the
step loop; level 2 additionally drops the KV cache pages to free HBM (they
are re-zeroed on wake).
"""

from __future__ import annotations

import asyncio
import threading
import time
import uuid
from typing import AsyncIterator, Dict, List, Optional, Sequence as Seq

from ..logging_utils import init_logger
from ..obs.engine_telemetry import ENGINE_TELEMETRY
from .config import EngineConfig
from .engine import LLMEngine, RequestOutput
from .sequence import SamplingParams

logger = init_logger(__name__)

_SENTINEL = object()


class AsyncLLMEngine:
    def __init__(self, cfg: EngineConfig, mesh=None):
        self.engine = LLMEngine(cfg, mesh)
        self._lock = threading.Lock()  # guards scheduler/engine mutation
        self._work = threading.Event()
        self._stop = False
        self._sleeping = False
        self._sleep_level = 0
        self._draining = False
        self._queues: Dict[str, asyncio.Queue] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        # Submission/abort mailboxes drained by the step thread, so the
        # asyncio loop never contends for the engine lock (a jitted step can
        # hold it for hundreds of ms — taking it on the loop would stall
        # every connection, including /health).
        self._submit_lock = threading.Lock()
        self._pending_adds: list = []
        self._pending_aborts: list = []
        # Step-loop health for the composite /health check.
        self.last_step_time = time.time()
        self.step_error: Optional[str] = None
        # Warmup precompilation gate (engine/precompile.py): the step
        # thread compiles the shape-bucket lattice before its first step;
        # /ready reports 503 and router discovery keeps the engine
        # unroutable until this flips. Requests submitted meanwhile queue
        # in the mailboxes — /health stays green (liveness != readiness).
        self._warming = cfg.warmup != "off"
        self.warmup_error: Optional[str] = None

    # -- lifecycle --------------------------------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        self._loop = loop or asyncio.get_event_loop()
        self._thread = threading.Thread(
            target=self._run, name="engine-step-loop", daemon=True
        )
        self._thread.start()

    def shutdown(self) -> None:
        self._stop = True
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if getattr(self.engine, "kv_publisher", None) is not None:
            self.engine.kv_publisher.shutdown()

    def is_healthy(self) -> bool:
        return (
            self.step_error is None
            and self._thread is not None
            and self._thread.is_alive()
        )

    @property
    def warming(self) -> bool:
        """True while the startup precompile pass is still running."""
        return self._warming

    @property
    def ready(self) -> bool:
        """Readiness (the /ready contract): healthy, warmed, awake, and
        accepting work. Distinct from liveness — a warming, sleeping, or
        draining engine is alive but must receive no new traffic."""
        return (
            self.is_healthy()
            and not self._warming
            and not self._sleeping
            and not self._draining
        )

    # -- sleep / wake -----------------------------------------------------

    @property
    def sleeping(self) -> bool:
        return self._sleeping

    def sleep(self, level: int = 1) -> None:
        self._sleeping = True
        self._sleep_level = level
        if level >= 2:
            with self._lock:
                # Dropping HBM pages invalidates every block the prefix maps
                # point at — clear them (and abort in-flight work) or later
                # prompts would adopt zeroed pages as cache hits.
                self.engine.clear_kv_state()
                self.engine.runner.drop_kv_cache()
            self._sentinel_all()
        logger.info("engine sleeping (level %d)", level)

    def wake_up(self) -> None:
        if self._sleep_level >= 2:
            with self._lock:
                self.engine.runner.restore_kv_cache()
        self._sleeping = False
        self._sleep_level = 0
        self._work.set()
        logger.info("engine awake")

    # -- drain ------------------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Stop accepting new sequences; in-flight ones keep decoding to
        completion (the step loop is untouched — only the HTTP admission
        gate closes). Router-side discovery marks draining engines
        unroutable; /undrain reverses."""
        self._draining = True
        logger.info("engine draining (in-flight sequences will finish)")

    def undrain(self) -> None:
        self._draining = False
        logger.info("engine accepting new sequences again")

    def num_inflight(self) -> int:
        # Swapped (preempted) sequences are still pending work — a drain
        # that ignored them would let preStop complete with generations
        # parked mid-flight.
        stats = self.engine.stats()
        return int(
            stats.get("num_requests_running", 0)
            + stats.get("num_requests_waiting", 0)
            + stats.get("num_requests_swapped", 0)
        )

    # -- submission -------------------------------------------------------

    async def generate(
        self,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[Seq[int]] = None,
        sampling: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        lora_name: Optional[str] = None,
        deadline: Optional[float] = None,
        tenant: Optional[str] = None,
        tenant_class: Optional[str] = None,
        kv_transfer: Optional[dict] = None,
    ) -> AsyncIterator[RequestOutput]:
        if self.step_error is not None:
            raise RuntimeError(f"engine is failed: {self.step_error}")
        rid = request_id or f"req-{uuid.uuid4().hex[:16]}"
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = queue
        finished = False
        try:
            with self._submit_lock:
                self._pending_adds.append(
                    (
                        rid,
                        dict(
                            prompt=prompt,
                            prompt_token_ids=prompt_token_ids,
                            sampling=sampling,
                            # Monotonic, matching Sequence queue/TTFT
                            # bookkeeping and deadline shedding.
                            arrival_time=time.monotonic(),
                            lora_name=lora_name,
                            deadline=deadline,
                            tenant=tenant,
                            tenant_class=tenant_class,
                            kv_transfer=kv_transfer,
                        ),
                    )
                )
            self._work.set()
            while True:
                item = await queue.get()
                if item is _SENTINEL:
                    break
                if isinstance(item, Exception):
                    finished = True  # never admitted: nothing to reclaim
                    raise item
                yield item
                if item.finished:
                    finished = True
                    break
        finally:
            self._queues.pop(rid, None)
            if not finished:  # client went away mid-stream: reclaim pages
                with self._submit_lock:
                    self._pending_aborts.append(rid)
                self._work.set()

    async def abort(self, request_id: str) -> bool:
        with self._submit_lock:
            self._pending_aborts.append(request_id)
        self._work.set()
        q = self._queues.get(request_id)
        if q is not None:
            q.put_nowait(_SENTINEL)
        return True

    # -- engine thread ----------------------------------------------------

    def _drain_mailboxes(self) -> None:
        with self._submit_lock:
            adds, self._pending_adds = self._pending_adds, []
            aborts, self._pending_aborts = self._pending_aborts, []
        with self._lock:
            for rid in aborts:
                self.engine.abort_request(rid)
            for rid, kwargs in adds:
                if rid in self._queues:  # skip if the client already left
                    try:
                        self.engine.add_request(rid, **kwargs)
                    except Exception as e:  # noqa: BLE001 — per-request error
                        logger.warning("add_request %s failed: %s", rid, e)
                        # Surface the error to the waiting client (HTTP 400
                        # for ValueError) instead of an empty 200 stream.
                        self._error_one(rid, e)

    def _sentinel_one(self, rid: str) -> None:
        if self._loop is None:
            return
        self._loop.call_soon_threadsafe(
            lambda: self._queues.get(rid) and self._queues[rid].put_nowait(_SENTINEL)
        )

    def _error_one(self, rid: str, exc: Exception) -> None:
        if self._loop is None:
            return
        self._loop.call_soon_threadsafe(
            lambda: self._queues.get(rid) and self._queues[rid].put_nowait(exc)
        )

    def _run(self) -> None:
        logger.info("engine step loop started")
        ENGINE_TELEMETRY.watch_collections()
        if self._warming:
            # Precompile on the step thread: the asyncio loop keeps
            # serving /health and /ready while the lattice compiles, and
            # no device step can interleave with a warmup dispatch.
            try:
                self.engine.precompile()
            except Exception as e:  # noqa: BLE001 — serve anyway: the
                # lattice shapes that did compile are warm, the rest
                # compile on demand (the pre-warmup behavior); readiness
                # still flips so the pod is not wedged forever.
                logger.exception("warmup precompile failed")
                self.warmup_error = str(e)
            self._warming = False
            self._work.set()
        # Every moment of this loop lies in one phase (obs/engine_telemetry
        # ``phase``): intake, no_work, or the engine's own step.
        outputs: List[RequestOutput] = []
        while not self._stop:
            with ENGINE_TELEMETRY.phase("intake"):
                self._publish(outputs)
                outputs = []
                self._drain_mailboxes()
                stepping = not self._sleeping and self.engine.has_work()
                if stepping:
                    self._lock.acquire()
            if not stepping:
                with ENGINE_TELEMETRY.phase("no_work"):
                    self._work.wait(timeout=0.05)
                    self._work.clear()
                self.last_step_time = time.time()
                continue
            try:
                try:
                    outputs = self.engine.step()
                finally:
                    self._lock.release()
                self.last_step_time = time.time()
            except Exception as e:  # noqa: BLE001 — surface via /health
                logger.exception("engine step failed")
                # Post-mortem BEFORE teardown: freeze the flight ring with
                # the failing step still at its tail (served at
                # GET /debug/flight for as long as the pod lives, and in
                # the log for after it doesn't).
                try:
                    snap = self.engine.flight.snapshot(
                        "fatal", detail={"error": str(e)}
                    )
                    tail = snap["records"][-3:]
                    logger.error(
                        "flight snapshot (fatal): %d steps recorded, tail=%s",
                        snap["total_steps"], tail,
                    )
                except Exception:  # noqa: BLE001 — never mask the real error
                    pass
                self.step_error = str(e)
                with self._lock:
                    # Drain the scheduler so the loop doesn't spin hot on the
                    # same failure; queued requests get sentinels (callers see
                    # truncated streams) and new submissions are refused.
                    self.engine.abort_all_requests()
                self._sentinel_all()
        self._publish(outputs)

    def _publish(self, outputs: List[RequestOutput]) -> None:
        if outputs and self._loop is not None:
            self._loop.call_soon_threadsafe(self._dispatch, outputs)

    def _dispatch(self, outputs: List[RequestOutput]) -> None:
        for out in outputs:
            q = self._queues.get(out.request_id)
            if q is not None:
                q.put_nowait(out)

    def _sentinel_all(self) -> None:
        if self._loop is None:
            return

        def _do():
            for q in self._queues.values():
                q.put_nowait(_SENTINEL)

        self._loop.call_soon_threadsafe(_do)
