"""The step loop's phases (docs/observability.md "Profiling"): one mechanism
(``ENGINE_TELEMETRY.phase``), two sinks.

- Under ``jax.profiler`` on the CPU, a tiny engine's step thread writes
  every ``pst.*`` span of the table: ``pst.intake`` / ``pst.no_work`` /
  ``pst.step`` side by side, and inside each step schedule, batch_build,
  launch, wait and postprocess plus one ``pst.step_info`` with the step's
  metadata; the phases leave almost none of a step uncovered.
- ``pst_engine_step_phase_seconds`` counts one observation per phase per
  step, however many spans a phase had in it.
- ``POST /debug/profile`` starts and stops the profiler off the event loop,
  with the Python tracer off, and says how long both took.
- A phase knows whether its thread ran: ``pst_engine_step_offcpu_seconds``
  once a step; the fetch counts its polls and keeps the longest time
  between two; a cycle past the flight recorder's bar is a stall, provoked
  here in a real engine by a ``postprocess`` that spins (``host_work``) and
  by a full collection over a large graph (``gc``), and each leaves its
  counter, its snapshot and its line in the log.
"""

import asyncio
import gc
import logging
import threading
import time

import aiohttp
import jax
import numpy as np
import pytest
from aiohttp import web

from perf import host_trace
from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.runner import _fetch
from production_stack_tpu.engine.server import create_engine_app
from production_stack_tpu.obs import ENGINE_TELEMETRY, ENGINE_TELEMETRY_REGISTRY
from production_stack_tpu.obs.flight import FlightRecorder, stall_cause

IN_STEP = ("schedule", "batch_build", "launch", "wait", "postprocess")


def _cfg():
    return EngineConfig(model="tiny-llama-debug", max_model_len=256,
                        block_size=16, num_kv_blocks=64, overlap_decode=False)


def _samples(family: str, suffix: str) -> dict:
    """{labels' values: value} of one sample name of a metric family."""
    return {
        tuple(smp.labels.values()): smp.value
        for metric in ENGINE_TELEMETRY_REGISTRY.collect()
        if metric.name == family
        for smp in metric.samples if smp.name == family + suffix
    }


def _phase_counts() -> dict:
    """{(phase, kind): observations} of pst_engine_step_phase_seconds."""
    return _samples("pst_engine_step_phase_seconds", "_count")


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


async def _generate(engine, prompt, n):
    async for _ in engine.generate(
            prompt_token_ids=prompt,
            sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                    ignore_eos=True)):
        pass


@pytest.fixture
def traced_run(tmp_path):
    """Two requests through the async engine under the profiler (Python
    tracer off, as /debug/profile sets it): the step thread's pst.* events
    and the histogram's growth meanwhile."""
    ENGINE_TELEMETRY.reset_for_tests()

    async def run():
        engine = AsyncLLMEngine(_cfg())
        engine.start(asyncio.get_running_loop())
        try:
            await _generate(engine, [1, 2, 3, 4, 5], 3)  # compiles, untraced
            before = _phase_counts()
            offcpu = _samples("pst_engine_step_offcpu_seconds", "_count")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                await asyncio.gather(_generate(engine, [5, 6, 7, 8, 9, 10], 4),
                                     _generate(engine, [3, 4, 5], 4))
                await asyncio.sleep(0.15)  # the loop finds nothing to step
            finally:
                jax.profiler.stop_trace()
            after = _phase_counts()
            offcpu = _grown(
                offcpu, _samples("pst_engine_step_offcpu_seconds", "_count"))
        finally:
            engine.shutdown()
        return {**_grown(before, after),
                **{("offcpu",) + k: v for k, v in offcpu.items()}}

    grown = asyncio.run(run())
    trace = next(tmp_path.rglob("*.xplane.pb"))
    events = host_trace._step_thread(host_trace.extract(str(trace)))
    return events, grown


def _inside(events, outer):
    s, e = outer[1], outer[1] + outer[2]
    return [ev for ev in events
            if ev is not outer and ev[1] >= s and ev[1] + ev[2] <= e]


def test_every_phase_of_the_table_in_every_step(traced_run):
    events, _ = traced_run
    # a collection or a stall may fall into any capture; nothing else may
    names = {ev[0] for ev in events} - {"pst.gc", "pst.stall"}
    assert names == {"pst." + p for p in IN_STEP + ("step", "step_info",
                                                    "intake", "no_work",
                                                    "ready")}
    # each program launched under the capture is stamped once when a poll
    # sees it ready (a zero-length pst.ready: the engine's clock for the
    # device), inside the wait that saw it or the launch that asked
    ready = [ev for ev in events if ev[0] == "pst.ready"]
    launched = [ev for ev in events if ev[0] == "pst.launch"]
    assert 0 < len(ready) <= len(launched) + 1
    for ev in ready:
        assert ev[2] < 1e5 and set(ev[3]) == {  # ns: a span of no length
            "kind", "bucket", "service_us", "queued_us", "seen"}
        assert ev[3]["kind"] in ("prefill", "decode")
        assert ev[3]["seen"] in ("poll", "late") and ev[3]["service_us"] >= 0
    steps = [ev for ev in events if ev[0] == "pst.step"]
    assert len(steps) >= 4  # a prefill step and three decode steps at least
    covered = total = 0.0
    for step in steps:
        inner = _inside(events, step)
        info = [ev[3] for ev in inner if ev[0] == "pst.step_info"]
        assert len(info) == 1, inner
        # a decode step says what its rows hold in common pages as well
        shared = ({"shared_kv_tokens", "shared_rows"}
                  if info[0]["kind"] == "decode" else set())
        assert set(info[0]) == {"kind", "bucket", "rows", "new_tokens",
                                "kv_tokens", "kv_pages"} | shared
        assert info[0]["kind"] in ("prefill", "decode")
        assert 1 <= info[0]["rows"] <= 2 <= info[0]["kv_tokens"]
        assert info[0]["new_tokens"] >= info[0]["rows"] <= info[0]["kv_pages"]
        for phase in IN_STEP:
            spans = [ev for ev in inner if ev[0] == "pst." + phase]
            assert spans, (phase, inner)
            if phase != "schedule":  # the phases of a step carry its kind
                assert {ev[3].get("kind") for ev in spans} == {info[0]["kind"]}
            covered += sum(ev[2] for ev in spans)
        total += step[2]
    # what a step spends outside its phases is glue of microseconds
    assert covered / total > 0.75
    # outside the steps the loop is in intake or in no_work, never bare:
    # every step and every idle wait is preceded by exactly one intake
    idle = [ev for ev in events if ev[0] == "pst.no_work"]
    intake = [ev for ev in events if ev[0] == "pst.intake"]
    assert idle and abs(len(intake) - len(steps) - len(idle)) <= 1
    segs = host_trace.leaf_segments(
        [(ev[1], ev[1] + ev[2], ev[0]) for ev in events
         if ev[0] != "pst.step_info"])
    for (_, end, _), (start, _, _) in zip(segs, segs[1:]):
        assert start - end < 2e6  # ns between neighbouring spans


def test_histogram_counts_one_observation_per_phase_per_step(traced_run):
    events, grown = traced_run
    kinds = [ev[3]["kind"] for ev in events if ev[0] == "pst.step_info"]
    steps = sum(1 for ev in events if ev[0] == "pst.step")
    # the trace may have cut the first and the last step; the histogram
    # saw those whole
    for kind in ("prefill", "decode"):
        n = grown[("step", kind)]
        assert kinds.count(kind) <= n <= kinds.count(kind) + 2
        for phase in IN_STEP[1:]:
            assert grown[(phase, kind)] == n, (phase, kind, grown)
        # the off-CPU time of a step's phases: once a step, as the step is
        assert grown[("offcpu", kind)] == n
    per_step = grown[("step", "prefill")] + grown[("step", "decode")]
    assert grown[("schedule", "")] == per_step >= steps
    assert grown[("intake", "")] >= per_step + grown[("no_work", "")] - 1
    # once an intake: the cycle's record carries it, the histogram not twice
    assert grown[("intake", "")] <= per_step + grown[("no_work", "")] + 1
    assert grown[("no_work", "")] >= 1


def test_step_info_names_the_open_step_and_costs_little():
    ENGINE_TELEMETRY.reset_for_tests()
    before = _phase_counts()
    with ENGINE_TELEMETRY.phase("step"):
        for _ in range(3):  # three spans of one phase: one observation
            with ENGINE_TELEMETRY.phase("launch", "decode"):
                pass
        ENGINE_TELEMETRY.step_info("decode", bucket="b1", rows=1)
    with ENGINE_TELEMETRY.phase("no_work"):
        pass
    grown = {k: v for k, v in _grown(before, _phase_counts()).items() if v}
    assert grown == {("step", "decode"): 1, ("launch", "decode"): 1,
                     ("no_work", ""): 1}
    t0 = time.perf_counter()
    for _ in range(2000):
        with ENGINE_TELEMETRY.phase("wait", "decode"):
            pass
    # ten a step must stay far below a 30 ms step; no trace is running
    assert (time.perf_counter() - t0) / 2000 < 200e-6


class _ReadyAfter:
    """What ``_fetch`` asks of an array, ready at the ``n``-th poll; the
    poll before ``slow`` comes late by ``late_s``."""

    def __init__(self, n, slow=0, late_s=0.0):
        self.n, self.slow, self.late_s, self.asked = n, slow, late_s, 0

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        self.asked += 1
        if self.asked == self.slow:
            time.sleep(self.late_s)
        return self.asked > self.n

    def __array__(self, dtype=None, copy=None):
        return np.arange(3)


def test_fetch_counts_its_polls_and_keeps_the_longest_gap():
    ENGINE_TELEMETRY.reset_for_tests()
    rec = FlightRecorder(capacity=8)
    ENGINE_TELEMETRY.attach_flight(rec)
    try:
        with ENGINE_TELEMETRY.phase("step"):
            assert list(_fetch(_ReadyAfter(5), "decode")) == [0, 1, 2]
            # two fetches of one step: the polls add up, the gap is the longest
            _fetch(_ReadyAfter(7, slow=3, late_s=0.05), "decode")
        paced = rec.records()[-1]
        assert paced["polls"] == 12
        assert 0.05 <= paced["poll_gap_max_s"] <= paced["wait_s"] <= paced["cycle_s"]
        with ENGINE_TELEMETRY.phase("step"):
            _fetch(_ReadyAfter(0), "decode")  # ready at once: no poll, no gap
        assert (rec.records()[-1]["polls"], rec.records()[-1]["poll_gap_max_s"]) == (0, 0.0)
        # a fetch outside any step (an embedding's, on another thread) counts nowhere
        _fetch(_ReadyAfter(3))
        assert rec.stats()["total_steps"] == 2
    finally:
        ENGINE_TELEMETRY.reset_for_tests()


def test_a_phase_knows_whether_its_thread_ran():
    """Off-CPU is wall less the thread's own CPU time, over the phases that
    never sleep (the intake among them: a stall of the interpreter's often
    lands there): a postprocess or an intake that sleeps is off the CPU,
    one that spins is on it, and a wait counts on neither side."""
    ENGINE_TELEMETRY.reset_for_tests()
    rec = FlightRecorder(capacity=8)
    ENGINE_TELEMETRY.attach_flight(rec)
    sums = _samples("pst_engine_step_offcpu_seconds", "_sum")
    try:
        with ENGINE_TELEMETRY.phase("intake"):
            time.sleep(0.01)
        with ENGINE_TELEMETRY.phase("step"):
            ENGINE_TELEMETRY.step_info("decode", bucket="b1")
            with ENGINE_TELEMETRY.phase("wait", "decode"):
                time.sleep(0.03)
            with ENGINE_TELEMETRY.phase("postprocess", "decode"):
                time.sleep(0.04)
                t0 = time.thread_time()
                while time.thread_time() - t0 < 0.02:
                    pass
        row = rec.records()[-1]
        assert 0.045 <= row["offcpu_s"] <= (
            row["intake_s"] + row["postprocess_s"] - 0.015)
        assert 0.02 <= row["thread_cpu_s"] <= row["process_cpu_s"] + 1e-3
        assert row["wait_s"] >= 0.03 and row["cycle_s"] >= 0.1
        assert row["intake_s"] >= 0.01 and row["gc_s"] == 0
        grown = _grown(sums, _samples("pst_engine_step_offcpu_seconds", "_sum"))
        assert grown[("decode",)] == pytest.approx(row["offcpu_s"], abs=1e-5)
        # an intake that finds nothing to step closes no cycle
        with ENGINE_TELEMETRY.phase("intake"):
            pass
        with ENGINE_TELEMETRY.phase("no_work"):
            pass
        assert rec.stats()["total_steps"] == 1
    finally:
        ENGINE_TELEMETRY.reset_for_tests()


def _spin(seconds: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def test_a_cycle_covers_one_stretch_on_the_wall_and_on_the_cpu_clocks():
    """In the loop a cycle starts where the one before ended, on all three
    clocks: what the thread burns between two cycles (the record's making)
    is in the next one's wall as it is in its CPU time, so it is not taken
    off the off-CPU time. A step no loop runs starts at its own opening."""
    ENGINE_TELEMETRY.reset_for_tests()
    rec = FlightRecorder(capacity=8)
    ENGINE_TELEMETRY.attach_flight(rec)

    def cycle(intake=True):
        if intake:
            with ENGINE_TELEMETRY.phase("intake"):
                pass
        with ENGINE_TELEMETRY.phase("step"):
            ENGINE_TELEMETRY.step_info("decode", bucket="b1")
            with ENGINE_TELEMETRY.phase("postprocess", "decode"):
                time.sleep(0.03)

    try:
        cycle()
        _spin(0.03)  # between the step's end and the next intake
        cycle()
        row = rec.records()[-1]
        assert row["cycle_s"] >= 0.06 and row["thread_cpu_s"] >= 0.03
        # the spin lies in the cycle and in none of its phases
        assert row["cycle_s"] - row["postprocess_s"] - row["intake_s"] >= 0.03
        # the sleep and nothing of the spin: 0.03, not 0.0
        assert 0.025 <= row["offcpu_s"] <= row["cycle_s"] - 0.03 + 2e-3
        # steps without a loop around them: the caller's time is the caller's
        _spin(0.03)
        time.sleep(0.03)
        cycle(intake=False)
        row = rec.records()[-1]
        assert row["cycle_s"] - row["postprocess_s"] < 0.02
        assert row["thread_cpu_s"] < 0.02
    finally:
        ENGINE_TELEMETRY.reset_for_tests()


def test_a_profiler_starting_under_a_cycle_is_no_stall():
    """``POST /debug/profile`` starts the capture inside
    ``profiler_starting``: it keeps the interpreter lock for tens of ms in
    every traced window, and the cycle it falls into is recorded and held
    to no bar, whether the start ends inside the cycle or after it. The
    same cycle without a capture is a stall."""
    ENGINE_TELEMETRY.reset_for_tests()
    rec = FlightRecorder(capacity=32)
    ENGINE_TELEMETRY.attach_flight(rec)
    stalls = _samples("pst_engine_stalls", "_total")
    lines = _Lines()
    log = logging.getLogger("production_stack_tpu.obs.engine_telemetry")
    log.addHandler(lines)

    def cycle(seconds, inside=lambda: None):
        with ENGINE_TELEMETRY.phase("intake"):
            pass
        with ENGINE_TELEMETRY.phase("step"):
            ENGINE_TELEMETRY.step_info("decode", bucket="b1")
            with ENGINE_TELEMETRY.phase("wait", "decode"):
                inside()
                time.sleep(seconds)

    def beside(fn):  # the capture starts on an executor's thread
        t = threading.Thread(target=fn)
        t.start()
        t.join()

    def start_and_finish():
        with ENGINE_TELEMETRY.profiler_starting():
            time.sleep(0.07)

    try:
        for _ in range(9):
            cycle(0.002)
        cycle(0.0, lambda: beside(start_and_finish))
        # or it is still starting when the cycle closes, and the next
        starting = ENGINE_TELEMETRY.profiler_starting()
        cycle(0.07, lambda: beside(starting.__enter__))
        cycle(0.07)
        beside(lambda: starting.__exit__(None, None, None))
        assert rec.snapshots() == [] and not lines.lines
        grown = _grown(stalls, _samples("pst_engine_stalls", "_total"))
        assert len(grown) == 7 and not any(grown.values())
        assert all(r["cycle_s"] >= 0.07 for r in rec.records()[-3:])
        cycle(0.07)  # the capture is up: cycles are held to the bar again
        assert len(rec.snapshots()) == 1 and len(lines.lines) == 1
        # and the three set no baseline
        assert rec.snapshots()[0]["detail"]["median_s"] < 0.01
    finally:
        log.removeHandler(lines)
        ENGINE_TELEMETRY.reset_for_tests()


def test_an_idle_loop_beside_it_takes_nothing_from_a_busy_one():
    """Two engines in one process (tests leave such threads behind): the
    idle one opens and drops a cycle every 50 ms, and the busy one's cycle
    is its own all the same."""
    ENGINE_TELEMETRY.reset_for_tests()
    rec = FlightRecorder(capacity=8)
    ENGINE_TELEMETRY.attach_flight(rec)

    def idle_tick():
        with ENGINE_TELEMETRY.phase("intake"):
            pass
        with ENGINE_TELEMETRY.phase("no_work"):
            pass

    def beside(fn):
        t = threading.Thread(target=fn)
        t.start()
        t.join()

    try:
        with ENGINE_TELEMETRY.phase("intake"):
            pass
        beside(idle_tick)
        with ENGINE_TELEMETRY.phase("step"):
            beside(idle_tick)
            with ENGINE_TELEMETRY.phase("wait", "decode"):
                time.sleep(0.01)
            ENGINE_TELEMETRY.polled(3, 0.004)
            beside(idle_tick)
        (row,) = rec.records()
        assert row["polls"] == 3 and row["wait_s"] >= 0.01 and row["intake_s"] > 0
        assert ENGINE_TELEMETRY._cycles == {}
    finally:
        ENGINE_TELEMETRY.reset_for_tests()


def _stall_counters() -> dict:
    return {(name, cause): v
            for name in ("pst_engine_stalls", "pst_engine_stall_seconds")
            for (cause,), v in _samples(name, "_total").items()}


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def _spin_once(engine, seconds):
    """The engine's ``_append_token``, spinning for ``seconds`` of the step
    thread's own CPU time the next time it is called: host work that runs
    long inside ``postprocess``."""
    real, armed = engine.engine._append_token, [True]

    def slow(*args, **kwargs):
        if armed and armed.pop():
            t0 = time.thread_time()
            while time.thread_time() - t0 < seconds:
                pass
        return real(*args, **kwargs)

    engine.engine._append_token = slow


def _collect_garbage(engine, seconds):
    """A full collection over a large graph from this thread: every thread
    of the process stands still meanwhile."""
    graph = [[i] for i in range(2_000_000)]
    t0 = time.perf_counter()
    gc.collect()
    assert time.perf_counter() - t0 > seconds, "the graph is too small to stall"
    del graph


@pytest.mark.parametrize("cause,provoke,phase", [
    ("host_work", _spin_once, "postprocess"),
    ("gc", _collect_garbage, None),
])
def test_a_stall_in_a_real_engine_names_its_cause(cause, provoke, phase):
    """Decode steps of a tiny engine take a few ms; once their bucket's bar
    is armed, one disturbance of 0.15 s and more is a stall of that cause,
    in the counters, in the recorder's snapshot and in the log."""
    ENGINE_TELEMETRY.reset_for_tests()
    lines = _Lines()
    log = logging.getLogger("production_stack_tpu.obs.engine_telemetry")
    log.addHandler(lines)

    async def run():
        engine = AsyncLLMEngine(_cfg())
        engine.start(asyncio.get_running_loop())
        try:
            await _generate(engine, [1, 2, 3, 4, 5], 3)  # compiles
            before = _stall_counters()
            stream = asyncio.create_task(_generate(engine, [5, 6, 7, 8], 200))
            flight = engine.engine.flight
            while flight.stats()["total_steps"] < 40:  # the bar is armed
                await asyncio.sleep(0.01)
            provoke(engine, 0.15)
            await stream
            return before, _stall_counters(), flight.snapshots()
        finally:
            engine.shutdown()

    try:
        before, after, snaps = asyncio.run(run())
    finally:
        log.removeHandler(lines)
        ENGINE_TELEMETRY.reset_for_tests()
    grown = {k: v for k, v in _grown(before, after).items() if v}
    tail = [s["detail"] for s in snaps if s["reason"] == "tail_outlier"]
    if cause == "host_work" and not any(d["cause"] == cause for d in tail):
        # Six test workers share this machine: when the spinning thread got
        # the CPU for less than half the stall, what held it was the
        # machine, and the record has to say so.
        (starved,) = [d for d in tail if d["phase"] == phase]
        assert starved["thread_cpu_s"] >= 0.15 <= starved["offcpu_s"]
        assert starved["offcpu_s"] >= starved["excess_s"] / 2
        cause = starved["cause"]
        assert cause in ("machine", "interpreter")
    # building the graph trips collections of its own, each a stall too
    stalls = grown[("pst_engine_stalls", cause)]
    assert stalls == 1 or (cause == "gc" and stalls >= 1), (grown, lines.lines)
    details = [d for d in tail if d["cause"] == cause]
    detail = details[-1]
    assert detail["cause"] == stall_cause(detail, detail["excess_s"])
    assert detail["kind"] == "decode" and detail["bucket"].startswith("b1")
    assert detail["excess_s"] >= 0.1
    seconds = grown[("pst_engine_stall_seconds", cause)]
    if stalls == 1:
        assert detail["excess_s"] == pytest.approx(seconds, abs=1e-3)
    else:  # the recorder keeps its last eight snapshots only
        assert seconds >= detail["excess_s"]
    assert detail["cycle_s"] > detail["bar_s"] >= 0.05 > detail["median_s"]
    assert {"intake_s", "schedule_s", "batch_build_s", "launch_s", "wait_s",
            "postprocess_s", "offcpu_s", "polls", "poll_gap_max_s",
            "process_cpu_s", "waiting", "running"} <= set(detail)
    if cause == "gc":
        assert detail["gc_s"] >= detail["excess_s"] / 2
    else:
        assert detail["phase"] == phase
        assert detail["thread_cpu_s"] >= 0.15 > detail["gc_s"]
    line = [ln for ln in lines.lines if f": {cause} (" in ln][-1]
    assert line.startswith(f"stall {detail['excess_s']:.2f} s in "
                           f"{detail['phase']} of decode {detail['bucket']}")
    assert "polls, longest gap" in line and "process CPU" in line
    assert "waiting 0, running 1" in line


class _Server:
    def __init__(self, **over):
        self.over = over

    async def __aenter__(self):
        self.engine = AsyncLLMEngine(_cfg())
        self.runner = web.AppRunner(create_engine_app(self.engine, **self.over))
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        self.engine.start(asyncio.get_running_loop())
        return self

    async def __aexit__(self, *exc):
        self.engine.shutdown()
        await self.runner.cleanup()


async def test_debug_profile_leaves_the_loop_free(monkeypatch, tmp_path):
    """A stubbed profiler whose start and stop block until released: while
    either blocks, /health answers and a second capture is refused (409)."""
    gate = {"start": threading.Event(), "stop": threading.Event()}
    entered = {"start": threading.Event(), "stop": threading.Event()}
    seen = {}

    def blocking(which):
        def call(*args, **kwargs):
            seen[which] = (args, kwargs)
            entered[which].set()
            seen[which + "_released"] = gate[which].wait(10)
        return call

    async def until(event):
        for _ in range(500):
            if event.is_set():
                return
            await asyncio.sleep(0.01)
        raise AssertionError("the profiler stub was never entered")

    async with _Server(profiling=True) as server, \
            aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(5)) as sess:
        # once the engine is up on the CPU: the handler then sees a chip
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax.profiler, "start_trace", blocking("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace", blocking("stop"))

        async def capture():
            async with sess.post(f"{server.url}/debug/profile", json={
                    "duration_ms": 20, "dir": str(tmp_path / "p")}) as r:
                return r.status, await r.json()

        first = asyncio.create_task(capture())
        for which in ("start", "stop"):
            await until(entered[which])
            async with sess.get(f"{server.url}/health") as r:
                assert r.status == 200
            status, _ = await capture()
            assert status == 409
            await asyncio.sleep(0.05)
            gate[which].set()
        status, body = await first
    assert status == 200 and body["status"] == "ok"
    assert seen["start_released"] and seen["stop_released"]
    assert body["start_s"] >= 0.05 and body["stop_s"] >= 0.05
    (out_dir,), kwargs = seen["start"]
    assert out_dir == str(tmp_path / "p")
    assert kwargs["profiler_options"].python_tracer_level == 0
    assert kwargs["profiler_options"].host_tracer_level == 2
