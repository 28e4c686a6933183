"""TPU-engine telemetry: compiles, step durations, throughput, KV pressure.

The metrics PR 3 could not give the engine: everything here is fed from
the *device-dispatch* layer (``engine/runner.py``) and the scheduler, so a
mid-run XLA recompile, a padding-wasteful batch, or a slow startup phase
becomes a Prometheus series instead of a mystery p99 outlier.

Compile detection is the first-call-per-bucket heuristic the static-shape
design makes sound: the runner pads every step into a small set of bucket
shapes and ``jax.jit`` caches one executable per bucket, so the FIRST
dispatch of a (kind, bucket, static-flags) signature is the one that pays
tracing + XLA compilation — its wall time is recorded as the compile cost
and the event is queued so the engine can attach it to the victim
request's trace (a recompile shows up *inside* the request timeline that
absorbed it).

Like :data:`..obs.metrics.OBS_REGISTRY`, everything lives in a dedicated
registry appended to the engine's ``/metrics`` — the router never double
registers it, and the fake engine can serve the same names as plain text.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)


ENGINE_TELEMETRY_REGISTRY = CollectorRegistry()

# Compile times span "re-trace only" (~100 ms) to multi-minute 8B builds.
_COMPILE_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                    120.0, 300.0)
# Step times span sub-ms CPU toys to 100 s cold 20k prefills.
_STEP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 10.0, 30.0, 120.0)
_FILL_BUCKETS = (0.1, 0.25, 0.5, 0.625, 0.75, 0.875, 1.0)

compile_total = Counter(
    "pst_engine_compile",
    "XLA compilations observed at jitted dispatch (first call per shape "
    "bucket), by step kind and padded shape bucket",
    ["kind", "shape_bucket"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
compile_seconds = Histogram(
    "pst_engine_compile_seconds",
    "Wall time of compile-bearing dispatches (trace + XLA build + first "
    "execution), by step kind",
    ["kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_COMPILE_BUCKETS,
)
step_duration = Histogram(
    "pst_engine_step_duration_seconds",
    "Device step wall time (dispatch to fetch), by step kind and padded "
    "batch bucket; compile-bearing first calls excluded",
    ["kind", "batch_bucket"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_STEP_BUCKETS,
)
# Host gaps span "pipelined, zero by construction" to ~100 ms of serial
# bookkeeping between bursts on a busy host.
_HOST_GAP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25)
host_gap_seconds = Histogram(
    "pst_engine_host_gap_seconds",
    "Serial host wall between a decode step's device completion and the "
    "next decode dispatch (batch build, detokenization, stop scans, "
    "scheduler accounting on the critical path), by padded batch bucket; "
    "pipelined continuations record 0 — the device never idled",
    ["batch_bucket"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_HOST_GAP_BUCKETS,
)
# Step-loop phases are mostly under 10 ms (a launch, a poll, a batch
# build), so the buckets are fine there; a compile-bearing launch or a long
# idle wait lands in the coarse tail.
_PHASE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)
step_phase_seconds = Histogram(
    "pst_engine_step_phase_seconds",
    "Wall time of one phase of the engine's step loop (no_work, intake, "
    "step, schedule, batch_build, launch, wait, postprocess), by phase and "
    "step kind; phases inside a step are summed over the step and observed "
    "once when it ends. The same phases are written into a running "
    "jax.profiler trace as pst.<phase> spans",
    ["phase", "kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_PHASE_BUCKETS,
)
batch_fill_ratio = Histogram(
    "pst_engine_batch_fill_ratio",
    "Useful fraction of each padded device step (real rows*tokens over "
    "padded rows*tokens) — 1.0 means zero padding waste",
    ["kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_FILL_BUCKETS,
)
tokens_per_second = Gauge(
    "pst_engine_tokens_per_second",
    "Engine token throughput over a short sliding window, by step kind",
    ["kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
kv_page_occupancy = Gauge(
    "pst_engine_kv_page_occupancy",
    "Fraction of HBM KV pages in use",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
kv_page_high_watermark = Gauge(
    "pst_engine_kv_page_high_watermark",
    "Highest KV page occupancy fraction observed since engine start",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
preemptions_total = Counter(
    "pst_engine_preemptions",
    "Scheduler recompute preemptions (out of KV pages)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
swap_out_total = Counter(
    "pst_engine_swap_out",
    "Sequences swapped out by the scheduler (KV parked host-side)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
swap_in_total = Counter(
    "pst_engine_swap_in",
    "Sequences swapped back in by the scheduler (KV resumed)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
start_time_seconds = Gauge(
    "pst_engine_start_time_seconds",
    "Wall-clock time the engine's runner initialized (the alert rules "
    "gate recompile alerts on uptime so cold-start compiles never page)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
startup_seconds = Gauge(
    "pst_engine_startup_seconds",
    "Engine startup decomposition, process start to the first 200 of "
    "/ready: imports (process start to the engine's constructor), "
    "tokenizer, load (param materialization), shard (device placement + "
    "KV alloc + jit wiring), warmup (compile cache, allocator, scheduler), "
    "precompile (ahead-of-time shape-bucket lattice compilation), serve "
    "(constructor done to first ready, precompile excluded)",
    ["phase"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
warmup_coverage = Gauge(
    "pst_engine_warmup_coverage",
    "Warmup precompile coverage: shape buckets compiled over buckets in "
    "the enumerated lattice (1.0 = every padded shape live traffic can "
    "produce is already compiled)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
warmup_buckets = Gauge(
    "pst_engine_warmup_buckets",
    "Warmup lattice size, by state: total (enumerated) vs compiled "
    "(dispatched at warmup)",
    ["state"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
compile_cache_hits = Counter(
    "pst_engine_compile_cache_hits",
    "Persistent JAX compilation-cache hits (executable deserialized "
    "instead of rebuilt by XLA)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
compile_cache_misses = Counter(
    "pst_engine_compile_cache_misses",
    "Persistent JAX compilation-cache misses (fresh XLA build, entry "
    "written for the next restart)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
# Per-request cost attribution (docs/observability.md "Cost attribution"):
# each finished request's accumulated device-seconds, split by phase, and
# the per-tenant chip-time meter that extends PR 12's token metering into
# billing-grade chip-seconds.
_REQUEST_DEVICE_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                           0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 120.0)
request_device_seconds = Histogram(
    "pst_request_device_seconds",
    "Device-seconds attributed to one finished request, by phase: prefill "
    "(token-weighted share of its prefill steps) or decode (active-row "
    "share of its decode bursts/spec verifies)",
    ["phase"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_REQUEST_DEVICE_BUCKETS,
)
tenant_device_seconds = Counter(
    "pst_tenant_device_seconds",
    "Device-seconds attributed to finished requests, per tenant — the "
    "chip-time billing meter beside pst_tenant_usage_tokens",
    ["tenant"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
device_busy_seconds = Counter(
    "pst_engine_device_busy_seconds",
    "Cumulative host-timed wall of live-traffic step dispatches (warmup "
    "precompilation excluded): the host's clock around each step, not a "
    "device counter — the denominator per-request cost attribution is "
    "audited against (sum of request device-seconds must cover >= 90% of "
    "this)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)

_trace_annotation = None


def _annotation(name: str, **meta):
    """``jax.profiler.TraceAnnotation``, imported on first use: the router
    and the fake engine import ``obs`` without jax."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(name, **meta)


class _Phase:
    """One open phase of the step loop: a ``pst.<name>`` span in the
    profiler's trace (host and device then share a clock) and, on exit,
    its wall time in ``pst_engine_step_phase_seconds``. ``kind`` may be set
    until the phase closes (a step learns its kind while it runs); the
    trace's copy of it is fixed at entry."""

    __slots__ = ("_tel", "name", "kind", "_ann", "_t0")

    def __init__(self, tel: "EngineTelemetry", name: str, kind: str, meta: dict):
        self._tel = tel
        self.name = name
        self.kind = kind
        if kind:
            meta["kind"] = kind
        self._ann = _annotation("pst." + name, **meta)

    def __enter__(self) -> "_Phase":
        if self.name == "step":
            self._tel._step, self._tel._step_tid = self, threading.get_ident()
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._tel._phase_done(self, dt)


# Fresh runners must re-count compiles even when an earlier runner in the
# same process already compiled identical bucket shapes (jit caches are
# per-runner): each ModelRunner takes a distinct scope id into its keys.
_runner_scope = itertools.count()


def next_runner_scope() -> int:
    return next(_runner_scope)


class EngineTelemetry:
    """Process-wide sink the runner/scheduler/server feed.

    Thread-safe: dispatches run on the engine step thread and executor
    threads while ``/metrics`` refreshes from the asyncio loop.
    """

    _TOKEN_WINDOW_S = 10.0

    def __init__(self):
        self._lock = threading.Lock()
        self._seen_shapes: set = set()
        self._pending_compile_events: List[dict] = []
        self._compiles = 0
        # (monotonic, kind, tokens) samples for the throughput window.
        self._tok_samples: "deque[Tuple[float, str, int]]" = deque()
        # Kinds that ever reported tokens: their gauges must drop to 0
        # when the window empties instead of freezing at the last burst.
        self._tok_kinds: set = set()
        self._counter_last: Dict[str, float] = {}
        self._kv_hwm = 0.0
        # Persistent compilation-cache accounting (fed by the jax
        # monitoring listener precompile.configure_compile_cache installs).
        self._cache_hits = 0
        self._cache_misses = 0
        # The step phase open on the step thread, and the wall its inner
        # phases have summed so far: {(phase, kind): seconds}.
        self._step: Optional[_Phase] = None
        self._step_tid = 0
        self._step_acc: Dict[Tuple[str, str], float] = {}
        self._phase_children: Dict[Tuple[str, str], object] = {}
        # Flight-recorder sink (obs/flight.py): every live dispatch
        # forwards one ring record; the null recorder makes this free.
        from .flight import NULL_FLIGHT_RECORDER

        self._flight = NULL_FLIGHT_RECORDER
        # Live-traffic step-wall accumulator (host-timed) — the denominator
        # the cost attribution audit sums request costs against.
        self._device_busy_s = 0.0
        # --no-startup-phases: the gauges stay at 0 (helm
        # servingEngineSpec.observability.startupPhases).
        self.startup_enabled = True

    # -- model / startup ------------------------------------------------

    def record_start_time(self) -> None:
        start_time_seconds.set(time.time())

    def record_startup_phase(self, phase: str, seconds: float) -> None:
        if not self.startup_enabled:
            return
        startup_seconds.labels(phase=phase).set(max(seconds, 0.0))

    # -- warmup / persistent compile cache -------------------------------

    def set_warmup_coverage(self, compiled: int, total: int) -> None:
        """Buckets-compiled over buckets-in-lattice (the /ready story in
        one gauge; updated as the precompiler walks the lattice)."""
        warmup_buckets.labels(state="total").set(max(total, 0))
        warmup_buckets.labels(state="compiled").set(max(compiled, 0))
        warmup_coverage.set(compiled / total if total > 0 else 0.0)

    def record_cache_event(self, hit: bool) -> None:
        """One persistent-compilation-cache lookup outcome (from the jax
        monitoring listener)."""
        with self._lock:
            if hit:
                self._cache_hits += 1
            else:
                self._cache_misses += 1
        (compile_cache_hits if hit else compile_cache_misses).inc()

    def cache_stats(self) -> "Tuple[int, int]":
        """(hits, misses) observed since process start — bench and the
        warm-restart e2e assert zero fresh misses on a warm restart."""
        with self._lock:
            return self._cache_hits, self._cache_misses

    # -- flight recorder / cost attribution ------------------------------

    def attach_flight(self, recorder) -> None:
        """Install the engine's flight recorder as the dispatch sink
        (obs/flight.py). One recorder per engine; re-attachment replaces
        (fresh engines in one process must not write a dead ring)."""
        from .flight import NULL_FLIGHT_RECORDER

        self._flight = recorder if recorder is not None else NULL_FLIGHT_RECORDER

    @property
    def flight(self):
        return self._flight

    def device_busy_seconds(self) -> float:
        """Cumulative live-traffic dispatch wall since process start (or
        the last reset) — warmup precompilation excluded."""
        with self._lock:
            return self._device_busy_s

    def record_request_cost(
        self, tenant: str, prefill_s: float, decode_s: float
    ) -> None:
        """One finished request's attributed device time → the per-phase
        histograms and the per-tenant chip-time meter."""
        prefill_s = max(prefill_s, 0.0)
        decode_s = max(decode_s, 0.0)
        if prefill_s > 0:
            request_device_seconds.labels(phase="prefill").observe(prefill_s)
        if decode_s > 0:
            request_device_seconds.labels(phase="decode").observe(decode_s)
        total = prefill_s + decode_s
        if total > 0:
            tenant_device_seconds.labels(
                tenant=str(tenant or "default")[:64]
            ).inc(total)

    # -- dispatch-level telemetry ---------------------------------------

    def record_dispatch(
        self,
        kind: str,
        shape_key: tuple,
        seconds: float,
        *,
        batch_bucket: str,
        tokens: int = 0,
        fill_ratio: Optional[float] = None,
        count_busy: bool = True,
    ) -> bool:
        """Record one device dispatch; returns True when this was the
        first call for its shape bucket (i.e. it paid a compile).

        ``count_busy=False`` marks warmup-precompile dispatches: they
        compile real executables but serve no request, so they stay out
        of the device-busy denominator and the flight ring."""
        seconds = max(seconds, 0.0)
        with self._lock:
            compiled = shape_key not in self._seen_shapes
            if compiled:
                self._seen_shapes.add(shape_key)
                self._compiles += 1
                self._pending_compile_events.append({
                    "kind": kind,
                    "shape_bucket": batch_bucket,
                    "seconds": round(seconds, 3),
                })
            if tokens > 0:
                now = time.monotonic()
                self._tok_samples.append((now, kind, tokens))
                self._refresh_throughput_locked(now)
            if count_busy:
                self._device_busy_s += seconds
        if count_busy:
            device_busy_seconds.inc(seconds)
            # Flight ring (obs/flight.py): one bounded record per live
            # dispatch, with the scheduler/KV state the engine's probe
            # supplies — the post-mortem trail for any step that stalls.
            self._flight.record_step(
                kind, batch_bucket, seconds, compiled=compiled, tokens=tokens
            )
        if compiled:
            compile_total.labels(kind=kind, shape_bucket=batch_bucket).inc()
            compile_seconds.labels(kind=kind).observe(seconds)
        else:
            # Compile-bearing calls are excluded from the step histogram so
            # its percentiles describe steady-state steps, not XLA builds.
            step_duration.labels(
                kind=kind, batch_bucket=batch_bucket
            ).observe(seconds)
        if fill_ratio is not None:
            batch_fill_ratio.labels(kind=kind).observe(
                min(max(fill_ratio, 0.0), 1.0)
            )
        return compiled

    def record_host_gap(
        self, batch_bucket: str, seconds: float,
        request_id: "Optional[str]" = None,
    ) -> None:
        """One decode-loop host gap (engine/runner.py host-gap accounting):
        the serial host wall between a decode step's completion and the
        next decode dispatch. Pipelined continuations record 0.0 — the
        continuation was dispatched before the previous burst's tokens
        were read, so the device ran the two back-to-back.

        ``request_id`` (one sequence of the gap-closing burst) attaches
        as an OpenMetrics exemplar: a slow host-gap bucket links to the
        ``/debug/requests?request_id=`` timeline that absorbed it."""
        seconds = max(seconds, 0.0)
        # The gap closes AT the next decode dispatch: hand it to the
        # flight ring so that dispatch's record carries it.
        self._flight.note_host_gap(seconds)
        child = host_gap_seconds.labels(batch_bucket=batch_bucket)
        if request_id:
            child.observe(seconds, exemplar={"request_id": str(request_id)[:48]})
        else:
            child.observe(seconds)

    # -- step-loop phases (pst.* spans + pst_engine_step_phase_seconds) --

    def phase(self, name: str, kind: str = "", **meta) -> _Phase:
        """Context manager around one phase of the step loop; see
        :class:`_Phase`. ``phase("step")`` is the whole of one engine step:
        the phases opened inside it on the same thread are summed per
        (phase, kind) and observed once when the step closes, so the
        histogram counts one observation per phase per step however many
        spans the trace shows."""
        return _Phase(self, name, kind, meta)

    def step_info(self, kind: str, **meta) -> None:
        """What the open step turned out to be, known only once it is
        scheduled and its batch is built: a zero-length ``pst.step_info``
        span carrying the metadata (a ``TraceAnnotation`` takes its own at
        entry, so ``pst.step`` cannot), and the step's ``kind`` label."""
        with _annotation("pst.step_info", kind=kind, **meta):
            pass
        step = self._step
        if step is not None and threading.get_ident() == self._step_tid:
            step.kind = kind

    def _observe_phase(self, name: str, kind: str, seconds: float) -> None:
        child = self._phase_children.get((name, kind))
        if child is None:
            child = self._phase_children[(name, kind)] = (
                step_phase_seconds.labels(phase=name, kind=kind)
            )
        child.observe(seconds)

    def _phase_done(self, phase: _Phase, seconds: float) -> None:
        if phase.name == "step":
            acc, self._step_acc, self._step = self._step_acc, {}, None
            for (name, kind), total in acc.items():
                self._observe_phase(name, kind, total)
        elif self._step is not None and threading.get_ident() == self._step_tid:
            key = (phase.name, phase.kind)
            self._step_acc[key] = self._step_acc.get(key, 0.0) + seconds
            return
        self._observe_phase(phase.name, phase.kind, seconds)

    def _refresh_throughput_locked(self, now: float) -> None:
        cutoff = now - self._TOKEN_WINDOW_S
        while self._tok_samples and self._tok_samples[0][0] < cutoff:
            self._tok_samples.popleft()
        per_kind: Dict[str, int] = {}
        for _, kind, toks in self._tok_samples:
            self._tok_kinds.add(kind)
            per_kind[kind] = per_kind.get(kind, 0) + toks
        span = (
            max(now - self._tok_samples[0][0], 0.5)
            if self._tok_samples else 1.0
        )
        # Kinds with no samples left in the window read 0, not their last
        # burst's value — an idle engine must look idle.
        for kind in self._tok_kinds:
            tokens_per_second.labels(kind=kind).set(
                per_kind.get(kind, 0) / span
            )

    # -- compile events → request traces --------------------------------

    def drain_compile_events(self) -> List[dict]:
        """Compile events recorded since the last drain (the engine
        attaches them to the step's in-flight request traces)."""
        with self._lock:
            events, self._pending_compile_events = (
                self._pending_compile_events, []
            )
        return events

    def compile_count(self) -> int:
        """Total compiles observed since process start."""
        with self._lock:
            return self._compiles

    # -- scheduler / KV refresh (from LLMEngine.stats()) ----------------

    def _counter_to(self, counter, key: str, total: float) -> None:
        last = self._counter_last.get(key, 0.0)
        if total > last:
            counter.inc(total - last)
            self._counter_last[key] = total
        elif total < last:  # in-process reset: re-baseline
            if total > 0:
                counter.inc(total)
            self._counter_last[key] = total

    def refresh_from_stats(self, stats: dict) -> None:
        occ = float(stats.get("kv_cache_usage_perc", 0.0))
        kv_page_occupancy.set(occ)
        with self._lock:
            # /metrics scrapes keep the throughput window honest even when
            # no dispatch has run since the last burst.
            self._refresh_throughput_locked(time.monotonic())
            self._kv_hwm = max(self._kv_hwm, occ)
            hwm = self._kv_hwm
        kv_page_high_watermark.set(hwm)
        self._counter_to(
            preemptions_total, "preempt",
            float(stats.get("num_preemptions_total", 0.0)),
        )
        self._counter_to(
            swap_out_total, "swap_out",
            float(stats.get("kv_swap_out_total", 0.0)),
        )
        self._counter_to(
            swap_in_total, "swap_in",
            float(stats.get("kv_swap_in_total", 0.0)),
        )

    # -- tests ----------------------------------------------------------

    def reset_for_tests(self) -> None:
        with self._lock:
            self._seen_shapes.clear()
            self._pending_compile_events.clear()
            self._compiles = 0
            self._tok_samples.clear()
            self._tok_kinds.clear()
            self._counter_last.clear()
            self._kv_hwm = 0.0
            self._cache_hits = 0
            self._cache_misses = 0
            self._step = None
            self._step_acc = {}
            self._device_busy_s = 0.0
            self.startup_enabled = True
        from .flight import NULL_FLIGHT_RECORDER

        self._flight = NULL_FLIGHT_RECORDER


ENGINE_TELEMETRY = EngineTelemetry()


def render_engine_telemetry() -> bytes:
    """Prometheus exposition of the engine telemetry registry — appended
    to the engine's ``/metrics`` next to ``render_obs_metrics()``."""
    return generate_latest(ENGINE_TELEMETRY_REGISTRY)
