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

import contextlib
import gc
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

from ..logging_utils import init_logger
from .flight import NULL_FLIGHT_RECORDER, PHASE_AT, STALL_CAUSES

logger = init_logger(__name__)

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
    "The host's wall around one dispatch call, by step kind and padded "
    "batch bucket; compile-bearing first calls excluded. Of a chained decode "
    "step that is the launch of the next program and the fetch of the one "
    "before, of a prefill launched behind a chain the launch alone: not "
    "the device's time, which is pst_engine_device_step_seconds",
    ["kind", "batch_bucket"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_STEP_BUCKETS,
)
# Service times between 5 and 100 ms in steps of 8 %: a median is good to
# one step there; coarse outside.
_SERVICE_BUCKETS = (
    0.0005, 0.001, 0.0025,
    *(round(0.005 * 1.08 ** i, 6) for i in range(39)),
    0.1, 0.125, 0.16, 0.2, 0.25, 0.5, 1.0, 2.5, 10.0, 60.0)
device_step_seconds = Histogram(
    "pst_engine_device_step_seconds",
    "Service time of one launched program on the device, by step kind: "
    "from the later of its launch and the program before it being seen "
    "ready to its own being seen ready by the fetch's poll (one poll, about "
    "1 ms, a stamp). Programs seen late (found ready with no ask just "
    "before that found them running: the host set the pace) are in the "
    "totals and not here",
    ["kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_SERVICE_BUCKETS,
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
step_offcpu_seconds = Histogram(
    "pst_engine_step_offcpu_seconds",
    "Off-CPU time of the step thread in one cycle of the step loop (an "
    "intake and the step after it) outside the wait phase: wall less the "
    "thread's own CPU time over intake, schedule, batch_build, launch and "
    "postprocess, observed once when the step ends, by step kind. There "
    "the thread never sleeps, so this is time it waited for the "
    "interpreter lock or for a core",
    ["kind"],
    registry=ENGINE_TELEMETRY_REGISTRY,
    buckets=_PHASE_BUCKETS,
)
stalls_total = Counter(
    "pst_engine_stalls",
    "Cycles of the step loop (one intake and the step after it) past the "
    "flight recorder's bar, by what held the step thread off: compile, gc, "
    "device, machine, interpreter, host_work, unknown",
    ["cause"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
stall_seconds_total = Counter(
    "pst_engine_stall_seconds",
    "Seconds stalled cycles took beyond the rolling median of their "
    "(kind, bucket), by cause",
    ["cause"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
# Every cause from the start: a window without a stall reads 0, not nothing.
_stall_children = {
    cause: (stalls_total.labels(cause=cause),
            stall_seconds_total.labels(cause=cause))
    for cause in STALL_CAUSES
}
gc_pause_seconds_total = Counter(
    "pst_engine_gc_pause_seconds",
    "Seconds the interpreter's collections of generations 1 and 2 paused "
    "the engine's process (every thread of it stands still meanwhile)",
    registry=ENGINE_TELEMETRY_REGISTRY,
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
    "(constructor done to first ready, precompile excluded); beside "
    "them, not one of their sum, program_first_use: what step shapes' "
    "first uses in the process took so far, in precompile or after it",
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
    "instead of rebuilt by XLA), and step programs loaded from the program "
    "store, which keeps those in its place",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
compile_cache_misses = Counter(
    "pst_engine_compile_cache_misses",
    "Persistent JAX compilation-cache misses (fresh XLA build, entry "
    "written for the next restart), and step programs built for the "
    "program store",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
# The program store (engine/program_store.py): what became of a step
# shape's first use in the process, and what that first use spent its time
# on. Every label from the start: a window without a first use reads 0.
PROGRAM_STORE_OUTCOMES = ("loaded", "built", "rejected")
FIRST_USE_PHASES = ("trace", "lower", "backend_compile", "cache_read",
                    "load", "write", "other")
program_store_total = Counter(
    "pst_engine_program_store",
    "Step programs met for the first time in the process where a program "
    "store is placed: loaded (the stored executable, nothing traced), "
    "built (traced, lowered and compiled, then stored), rejected (an entry "
    "that did not read, load or take its arguments: dropped and rebuilt)",
    ["outcome"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
program_first_use_seconds = Counter(
    "pst_engine_program_first_use_seconds",
    "Seconds step shapes' first uses in the process took, by what they "
    "were spent on: trace (the program's Python to a jaxpr), lower (the "
    "jaxpr to its module, kernels' bodies included), backend_compile (XLA "
    "and Mosaic, or the cache key where the cache had it), cache_read "
    "(XLA's persistent cache: read and load), load (the program store: "
    "read, decompress and load), write (a built program serialised into "
    "the store), other (argument handling and the first call)",
    ["phase"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
_store_children = {
    o: program_store_total.labels(outcome=o) for o in PROGRAM_STORE_OUTCOMES}
_first_use_children = {
    p: program_first_use_seconds.labels(phase=p) for p in FIRST_USE_PHASES}
# jax.monitoring's duration events, by the last part of their names, to
# the phase each feeds (engine/precompile.py installs the listener).
_FIRST_USE_EVENTS = {
    "jaxpr_trace_duration": "trace",
    "jaxpr_to_mlir_module_duration": "lower",
    "backend_compile_duration": "backend_compile",
    "cache_retrieval_time_sec": "cache_read",
}
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
    "Cumulative service time of the programs live traffic launched (warmup "
    "precompilation excluded), each counted when the step thread's poll "
    "sees it ready: ready less the later of its launch and the ready of "
    "the program before it, so over a busy stretch the sum is last ready "
    "less first start. The denominator per-request cost attribution is "
    "audited against (sum of request device-seconds must cover >= 90% of "
    "this)",
    registry=ENGINE_TELEMETRY_REGISTRY,
)
device_service_seconds = Counter(
    "pst_engine_device_service_seconds",
    "The busy counter's seconds by step kind and by how the program's end "
    "was seen: poll (a poll at most 2.5 ms earlier had found it running: "
    "the stamp is good to that) or late (none had: it ended at some moment "
    "before, and its interval holds what the host was late by and the "
    "device meanwhile idled)",
    ["kind", "seen"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
IDLE_STATES = ("host", "no_work")
device_idle_seconds = Counter(
    "pst_engine_device_idle_seconds",
    "Seconds between one program being seen ready and the launch of the "
    "next where nothing was queued behind it, by state: no_work (the step "
    "loop waited for work in between) or host (it did not: the device "
    "waited for the host). High by at most the launch phase's length a "
    "stretch",
    ["state"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
loop_seconds = Counter(
    "pst_engine_loop_seconds",
    "The step thread's wall by what the loop was doing: each cycle (an "
    "intake and the step after it) whole under the step's kind (decode, "
    "prefill, spec_verify; none for a step that dispatched nothing), each "
    "wait for work with the intake before it under no_work. Cycles begin "
    "where the last stretch ended, so the states' changes between two "
    "scrapes sum to the wall between them (to the stretch in progress)",
    ["state"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
loop_cycles = Counter(
    "pst_engine_loop_cycles",
    "Stretches counted into pst_engine_loop_seconds_total, by state",
    ["state"],
    registry=ENGINE_TELEMETRY_REGISTRY,
)
LOOP_STATES = ("decode", "prefill", "spec_verify", "none", "no_work")
# Every label from the start: a window without the state reads 0.
_idle_children = {
    st: device_idle_seconds.labels(state=st) for st in IDLE_STATES}
_loop_children = {
    st: (loop_seconds.labels(state=st), loop_cycles.labels(state=st))
    for st in LOOP_STATES}

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
    trace's copy of it is fixed at entry. ``t0`` and ``t1`` are the wall
    clock's readings at its ends (the runner's completion clock stamps a
    launch with its ``pst.launch``'s ``t1`` and reads no clock of its
    own). A ``wait`` also takes the thread's CPU clock at both ends: the
    cycle's account leaves the wait's share out (see :class:`_Cycle`)."""

    __slots__ = ("_tel", "name", "kind", "_ann", "t0", "t1", "_c0")

    def __init__(self, tel: "EngineTelemetry", name: str, kind: str, meta: dict):
        self._tel = tel
        self.name = name
        self.kind = kind
        if kind:
            meta["kind"] = kind
        self._ann = _annotation("pst." + name, **meta)

    def __enter__(self) -> "_Phase":
        if self.name == "step":
            self._tel._step_opened(self)
        elif self.name == "intake":
            self._tel._cycle_opened()
        self._ann.__enter__()
        if self.name == "wait":
            self._c0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        cpu = time.thread_time() - self._c0 if self.name == "wait" else 0.0
        self._ann.__exit__(*exc)
        self._tel._phase_done(self, self.t1 - self.t0, cpu)


class _Cycle:
    """What one cycle of the step loop (one ``pst.intake`` and the
    ``pst.step`` after it) has gathered so far for its flight record.

    Whether the thread ran is known for the cycle, not for each phase: a
    read of a CPU clock is a system call (6 us on the benchmark's host,
    where the clocks also tick in steps of 10 ms), so the step thread's
    clock is read where a cycle ends, which is where the next begins, and
    around each wait: four reads a cycle with the process's clock. The
    wall clock is read at the same places, so a cycle's wall and its CPU
    time cover the same stretch: from the end of the cycle before (its
    record's making included) to the end of this one."""

    __slots__ = ("t0", "thread_cpu0", "process_cpu0", "profiler_starts0",
                 "phases", "wait_cpu_s", "dispatches", "gc_s", "polls",
                 "poll_gap_max_s", "store_outcome", "device_s", "service_s",
                 "queued_s")

    def __init__(self, t0: float, thread_cpu0: float, process_cpu0: float,
                 profiler_starts0: int):
        self.t0 = t0
        self.thread_cpu0 = thread_cpu0
        self.process_cpu0 = process_cpu0
        self.profiler_starts0 = profiler_starts0
        # {(phase, kind): wall}: the intake, then the step's phases summed
        # over their spans
        self.phases: Dict[Tuple[str, str], float] = {}
        self.wait_cpu_s = 0.0
        # (kind, bucket, seconds, compiled, tokens) of each live dispatch
        self.dispatches: List[tuple] = []
        self.gc_s = 0.0
        self.polls = 0
        self.poll_gap_max_s = 0.0
        # what the program store made of a shape this cycle met first
        self.store_outcome = ""
        # The programs seen ready under the cycle (`record_ready`): their
        # service times summed, and of the last of them (the one the cycle
        # fetched) its own and how long, inside the cycle, it stood behind
        # the others.
        self.device_s = 0.0
        self.service_s = 0.0
        self.queued_s = 0.0


class _FirstUse:
    """One step shape's first use in the process, while it is open: the
    seconds of each phase and what the program store made of it."""

    __slots__ = ("seconds", "outcome")

    def __init__(self):
        self.seconds = dict.fromkeys(FIRST_USE_PHASES, 0.0)
        self.outcome = ""


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
        # By thread, the first use of a step shape that is open there
        # (`program_first_use`); and the seconds all of them took so far.
        self._first_use = threading.local()
        self._first_use_s = 0.0
        # The step phase open on the step thread, and by thread the cycle
        # its step closes (opened by the intake before it, or by the step
        # itself where nothing runs an intake). By thread: a second engine
        # of the process, idling, opens and drops a cycle every 50 ms.
        self._step: Optional[_Phase] = None
        self._step_tid = 0
        self._cycles: Dict[int, _Cycle] = {}
        # (thread, wall clock, its CPU clock, the process's) where the last
        # cycle ended: the next cycle of that thread starts from them.
        self._cpu_mark: Optional[Tuple[int, float, float, float]] = None
        # What the off-CPU histogram still owes: a cycle whose CPU clock
        # ticked past its wall reads below zero, and the next pays for it.
        self._offcpu_carry = 0.0
        self._phase_children: Dict[Tuple[str, str], object] = {}
        self._offcpu_children: Dict[str, object] = {}
        # Start of the collection that is running (perf_counter; 0 when
        # none is), its pst.gc span where it runs on the step thread, and
        # whether a cycle that closed meanwhile has already counted it.
        self._gc_t0 = 0.0
        self._gc_span = None
        self._gc_counted = False
        # Twice the captures begun, and one more while one is starting.
        self._profiler_starts = 0
        # Flight-recorder sink (obs/flight.py): one ring record a cycle of
        # the step loop; the null recorder makes this free.
        self._flight = NULL_FLIGHT_RECORDER
        # Live traffic's service time on the device (`record_ready`): the
        # denominator the cost attribution audit sums request costs against.
        self._device_busy_s = 0.0
        # Waits for work the loop has made: the runner's clock reads it at
        # a launch and at a ready, and files the idle between the two under
        # no_work where it moved.
        self.no_work_phases = 0
        self._service_children: Dict[Tuple[str, str], tuple] = {}
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
        monitoring listener), or a program store's: a step program it
        builds goes past XLA's cache, which reports nothing of it."""
        with self._lock:
            if hit:
                self._cache_hits += 1
            else:
                self._cache_misses += 1
        (compile_cache_hits if hit else compile_cache_misses).inc()

    @contextlib.contextmanager
    def program_first_use(self):
        """Around a step shape's first use in the process (the runner's
        holder of step programs opens it): what jax reports of tracing,
        lowering, compiling and its cache on this thread meanwhile is the
        first use's, and so is what the program store adds to the record it
        is handed (``seconds["load"]``, ``seconds["write"]``, ``outcome``).
        At the end the split goes to
        ``pst_engine_program_first_use_seconds_total{phase}``, the outcome
        to ``pst_engine_program_store_total`` and the sum of all first uses
        so far to ``pst_engine_startup_seconds{phase="program_first_use"}``."""
        use = self._first_use.open = _FirstUse()
        t0 = time.perf_counter()
        try:
            yield use
        finally:
            self._first_use.open = None
            wall = time.perf_counter() - t0
            sec = use.seconds
            # XLA times its cache inside its compile
            sec["backend_compile"] = max(
                sec["backend_compile"] - sec["cache_read"], 0.0)
            sec["other"] = max(wall - sum(sec.values()), 0.0)
            for phase, child in _first_use_children.items():
                child.inc(sec[phase])
            with self._lock:
                self._first_use_s += wall
                total = self._first_use_s
            self.record_startup_phase("program_first_use", total)
            if use.outcome:
                _store_children[use.outcome].inc()
                self.record_cache_event(use.outcome == "loaded")
                cycle = self._open_cycle()
                if cycle is not None:
                    cycle.store_outcome = use.outcome

    def first_use_event(self, event: str, seconds: float) -> None:
        """One of jax.monitoring's duration events, on the thread that
        compiles: counted where a first use is open there. A program's
        trace reports the traces of the jitted functions inside it too,
        each inside the outer one's time: the longest is the program's."""
        use = getattr(self._first_use, "open", None)
        if use is None:
            return
        phase = _FIRST_USE_EVENTS.get(event.rsplit("/", 1)[-1])
        if phase == "trace":
            use.seconds[phase] = max(use.seconds[phase], seconds)
        elif phase is not None:
            use.seconds[phase] += seconds

    def program_rejected(self) -> None:
        """A stored program that did not read, load or take its arguments."""
        _store_children["rejected"].inc()

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
        self._flight = recorder if recorder is not None else NULL_FLIGHT_RECORDER

    def device_busy_seconds(self) -> float:
        """Cumulative service time of live traffic's programs since process
        start (or the last reset) — warmup precompilation excluded."""
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
        device_s: float = 0.0,
    ) -> bool:
        """Record one device dispatch; returns True when this was the
        first call for its shape bucket (i.e. it paid a compile).
        ``seconds`` is the host's wall around the dispatch call (the step
        histogram's, and a compile's cost); the device's time is not known
        here and comes with `record_ready`. ``device_s``: the service time
        of a dispatch outside the step loop, which fetched what it launched
        (an embedding's encode), for its flight record.

        ``count_busy=False`` marks warmup-precompile dispatches: they
        compile real executables but serve no request, so they stay out
        of the flight ring."""
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
                # Appended here, summed at the scrape (refresh_from_stats):
                # nobody reads the gauge in between.
                now = time.monotonic()
                self._tok_samples.append((now, kind, tokens))
                self._drop_old_samples_locked(now)
        if count_busy:
            # Flight ring (obs/flight.py): a live dispatch of the open step
            # rides its cycle's record, whose last row takes the device
            # time the cycle saw (`_cycle_done`); any other (an embedding's
            # encode) is a record of its own.
            cycle = self._open_cycle()
            if cycle is not None:
                cycle.dispatches.append(
                    (kind, batch_bucket, 0.0, compiled, tokens))
            else:
                self._flight.record_step(
                    kind, batch_bucket, device_s, compiled=compiled,
                    tokens=tokens,
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

    def record_ready(
        self, kind: str, bucket: str, launched_at: float, start: float,
        ready: float, seen: str, idle_s: float = 0.0,
        idle_state: str = "host", live: bool = True,
    ) -> None:
        """One launched program seen ready (engine/runner.py ``_ReadyClock``;
        the three stamps are `perf_counter` readings the step thread had
        made anyway): ``start`` is the later of its launch and the ready of
        the program before it, so ``ready - start`` is its service time on
        a device that runs programs in launch order, and ``idle_s`` what
        lay between that earlier ready and this launch. To the busy
        counter, the service histogram (not a program ``seen`` late), the
        idle counter, the open cycle's record, and a zero-length
        ``pst.ready`` span beside the program's own end in a capture.
        ``live`` false: a warm-up's program, which moves the clock and no
        counter."""
        service = max(ready - start, 0.0)
        queued = max(start - launched_at, 0.0)
        with _annotation(
            "pst.ready", kind=kind, bucket=bucket,
            service_us=int(service * 1e6), queued_us=int(queued * 1e6),
            seen=seen,
        ):
            pass
        if not live:
            return
        with self._lock:
            self._device_busy_s += service
        device_busy_seconds.inc(service)
        children = self._service_children.get((kind, seen))
        if children is None:
            children = self._service_children[(kind, seen)] = (
                device_service_seconds.labels(kind=kind, seen=seen),
                device_step_seconds.labels(kind=kind))
        children[0].inc(service)
        if seen != "late":
            children[1].observe(service)
        if idle_s > 0:
            _idle_children[idle_state].inc(idle_s)
        cycle = self._open_cycle()
        if cycle is not None:
            cycle.device_s += service
            cycle.service_s = service
            # behind others inside this cycle: what it stood before the
            # cycle began was the cycle before's to wait out
            cycle.queued_s = max(start - max(launched_at, cycle.t0), 0.0)

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
        if self._on_step_thread():
            self._step.kind = kind

    def _on_step_thread(self) -> bool:
        """Whether the caller is the thread that has a step open."""
        return self._step is not None and threading.get_ident() == self._step_tid

    def _open_cycle(self) -> Optional[_Cycle]:
        """The cycle of the step open on the calling thread, if one is."""
        return self._cycles.get(self._step_tid) if self._on_step_thread() else None

    def _cycle_opened(self, follows: bool = True) -> None:
        """A cycle starts where the last stretch of this thread ended, a
        cycle or a wait for work (all three clocks from one mark), unless
        nothing ended here yet or no loop runs the steps (``follows``
        false: what lies between two steps is their caller's then)."""
        tid = threading.get_ident()
        mark = self._cpu_mark
        if follows and mark is not None and mark[0] == tid:
            self._cpu_mark = None
            self._cycles[tid] = _Cycle(*mark[1:], self._profiler_starts)
        else:
            self._cycles[tid] = _Cycle(
                time.perf_counter(), time.thread_time(), time.process_time(),
                self._profiler_starts)

    def _step_opened(self, step: _Phase) -> None:
        self._step, self._step_tid = step, threading.get_ident()
        if self._step_tid not in self._cycles:  # no intake preceded the step
            self._cycle_opened(follows=False)

    def polled(self, polls: int, gap_max_s: float) -> None:
        """How a fetch of the open step waited (engine/runner.py
        ``_fetch``): the polls it made and the longest time between two."""
        cycle = self._open_cycle()
        if cycle is not None:
            cycle.polls += polls
            if gap_max_s > cycle.poll_gap_max_s:
                cycle.poll_gap_max_s = gap_max_s

    def _observe_phase(self, name: str, kind: str, seconds: float) -> None:
        child = self._phase_children.get((name, kind))
        if child is None:
            child = self._phase_children[(name, kind)] = (
                step_phase_seconds.labels(phase=name, kind=kind)
            )
        child.observe(seconds)

    def _phase_done(self, phase: _Phase, seconds: float, cpu_s: float) -> None:
        if phase.name == "step":
            self._step = None
            cycle = self._cycles.pop(threading.get_ident(), None)
            if cycle is not None:  # None: reset_for_tests under an open step
                for (name, kind), wall in cycle.phases.items():
                    if name != "intake":  # observed when it closed
                        self._observe_phase(name, kind, wall)
                self._cycle_done(cycle, phase.kind)
        elif (cycle := self._open_cycle()) is not None:
            key = (phase.name, phase.kind)
            cycle.phases[key] = cycle.phases.get(key, 0.0) + seconds
            cycle.wait_cpu_s += cpu_s
            return
        elif phase.name == "intake":
            cycle = self._cycles.get(threading.get_ident())
            if cycle is not None:
                cycle.phases[("intake", "")] = seconds
        elif phase.name == "no_work":
            # The intake before it found nothing to step: its cycle is
            # dropped, and the stretch from where that began is the loop
            # account's `no_work`. The next cycle begins here.
            tid = threading.get_ident()
            dropped = self._cycles.pop(tid, None)
            self.no_work_phases += 1
            self._loop_account(
                "no_work", phase.t1 - dropped.t0 if dropped else seconds)
            self._cpu_mark = (
                tid, phase.t1, time.thread_time(), time.process_time())
        self._observe_phase(phase.name, phase.kind, seconds)

    def _loop_account(self, state: str, seconds: float) -> None:
        """One stretch of the step thread's wall, to the window account."""
        children = _loop_children.get(state)
        if children is None:
            children = _loop_children[state] = (
                loop_seconds.labels(state=state),
                loop_cycles.labels(state=state))
        children[0].inc(max(seconds, 0.0))
        children[1].inc()

    def _cycle_done(self, cycle: _Cycle, kind: str) -> None:
        """The cycle's account, to the off-CPU histogram and to the flight
        recorder; a cycle past the recorder's bar is a stall, and leaves
        its counters, one WARNING line and (under a capture) a zero-length
        ``pst.stall`` span."""
        now = time.perf_counter()
        thread_cpu, process_cpu = time.thread_time(), time.process_time()
        self._cpu_mark = (threading.get_ident(), now, thread_cpu, process_cpu)
        cycle_s = now - cycle.t0
        self._loop_account(kind or "none", cycle_s)
        sums = [0.0] * len(PHASE_AT)
        for (name, _), wall in cycle.phases.items():
            sums[PHASE_AT[name]] += wall
        # Outside its waits the thread wants the CPU throughout.
        thread_cpu_s = thread_cpu - cycle.thread_cpu0 - cycle.wait_cpu_s
        offcpu = cycle_s - sums[PHASE_AT["wait"]] - thread_cpu_s
        owed = offcpu + self._offcpu_carry
        self._offcpu_carry = min(owed, 0.0)
        child = self._offcpu_children.get(kind)
        if child is None:
            child = self._offcpu_children[kind] = (
                step_offcpu_seconds.labels(kind=kind))
        child.observe(max(owed, 0.0))
        gc_s = cycle.gc_s
        if self._gc_t0:
            # A collection on another thread that has not reported yet: it
            # is done (this thread runs again) and its callback waits for
            # the interpreter lock this thread took from it.
            gc_s += now - self._gc_t0
            self._gc_counted = True
        starts = self._profiler_starts
        # The record's last row takes the device time the cycle saw (a
        # cycle that only fetched, a chain's drain, has no row but its own).
        dispatches = cycle.dispatches
        if dispatches:
            dispatches[-1] = (*dispatches[-1][:2], cycle.device_s,
                              *dispatches[-1][3:])
        elif cycle.device_s:
            dispatches = [(kind or "none", "", cycle.device_s, False, 0)]
        stall = self._flight.record_cycle(
            dispatches, cycle_s,
            (*sums, max(offcpu, 0.0), max(thread_cpu_s, 0.0),
             process_cpu - cycle.process_cpu0, gc_s, cycle.polls,
             cycle.poll_gap_max_s, cycle.service_s, cycle.queued_s),
            kind=kind,
            # a capture that started under the cycle held it, not the engine
            held_to_bar=not (starts & 1 or starts != cycle.profiler_starts0),
        )
        if stall is None:
            return
        count, seconds = _stall_children[stall["cause"]]
        count.inc()
        seconds.inc(stall["excess_s"])
        # a first use through the program store says what became of it
        made = (f"{cycle.store_outcome}; "
                if stall["cause"] == "compile" and cycle.store_outcome else "")
        logger.warning(
            "stall %.2f s in %s of %s: %s (%s%s polls, longest gap %.1f ms, "
            "gc %.2f s, off-CPU %.2f s, thread CPU %.2f s, process CPU "
            "%.2f s; waiting %d, running %d)",
            stall["excess_s"], stall["phase"],
            f"{stall['kind']} {stall['bucket']}".strip(), stall["cause"],
            made, f"{stall['polls']:,}", stall["poll_gap_max_s"] * 1e3,
            stall["gc_s"], stall["offcpu_s"], stall["thread_cpu_s"],
            stall["process_cpu_s"], stall["waiting"], stall["running"],
        )
        with _annotation(
            "pst.stall", cause=stall["cause"], phase=stall["phase"],
            excess_ms=round(stall["excess_s"] * 1e3, 3),
        ):
            pass

    @contextlib.contextmanager
    def profiler_starting(self):
        """Around ``jax.profiler.start_trace`` (``POST /debug/profile``).
        Starting a capture keeps the interpreter lock for some 50 ms on the
        benchmark's host: a stall in every window that is profiled and in
        no other, of the measurement's making. A cycle it falls into is
        recorded and held to no bar."""
        self._profiler_starts += 1
        try:
            yield
        finally:
            self._profiler_starts += 1

    # -- collections (pst_engine_gc_pause_seconds_total, pst.gc) ---------

    def watch_collections(self) -> None:
        """Time the interpreter's collections from inside the program;
        called where the step thread starts. One ``gc.callbacks`` entry
        however often it is asked for."""
        if self._on_collection not in gc.callbacks:
            gc.callbacks.append(self._on_collection)

    def _on_collection(self, phase: str, info: dict) -> None:
        """A collection holds the interpreter lock from "start" to "stop",
        on whichever thread tripped it, and no other starts meanwhile. The
        callbacks themselves are Python: a thread that has waited out the
        collection takes the lock at their first instruction, so a cycle
        may close between the collection's end and its "stop"
        (``_cycle_done`` counts it then, and says so)."""
        if info["generation"] == 0:
            return
        if phase == "start":
            if self._on_step_thread():
                self._gc_span = _annotation(
                    "pst.gc", generation=info["generation"])
                self._gc_span.__enter__()
            self._gc_t0 = time.perf_counter()
            return
        t0, self._gc_t0 = self._gc_t0, 0.0
        pause = time.perf_counter() - t0
        span, self._gc_span = self._gc_span, None
        if span is not None:
            span.__exit__(None, None, None)
        gc_pause_seconds_total.inc(pause)
        counted, self._gc_counted = self._gc_counted, False
        if not counted:
            for cycle in list(self._cycles.values()):
                cycle.gc_s += pause

    def _drop_old_samples_locked(self, now: float) -> None:
        cutoff = now - self._TOKEN_WINDOW_S
        while self._tok_samples and self._tok_samples[0][0] < cutoff:
            self._tok_samples.popleft()

    def _refresh_throughput_locked(self, now: float) -> None:
        self._drop_old_samples_locked(now)
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
            self._first_use_s = 0.0
            self._step = None
            self._cycles.clear()
            self._cpu_mark = None
            self._offcpu_carry = 0.0
            self._device_busy_s = 0.0
            self.no_work_phases = 0
            self.startup_enabled = True
        self._flight = NULL_FLIGHT_RECORDER


ENGINE_TELEMETRY = EngineTelemetry()


def render_engine_telemetry() -> bytes:
    """Prometheus exposition of the engine telemetry registry — appended
    to the engine's ``/metrics`` next to ``render_obs_metrics()``."""
    return generate_latest(ENGINE_TELEMETRY_REGISTRY)
