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
"""

import asyncio
import threading
import time

import aiohttp
import jax
import pytest
from aiohttp import web

from perf import host_trace
from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import create_engine_app
from production_stack_tpu.obs import ENGINE_TELEMETRY, ENGINE_TELEMETRY_REGISTRY

IN_STEP = ("schedule", "batch_build", "launch", "wait", "postprocess")


def _cfg():
    return EngineConfig(model="tiny-llama-debug", max_model_len=256,
                        block_size=16, num_kv_blocks=64, overlap_decode=False)


def _phase_counts() -> dict:
    """{(phase, kind): observations} of pst_engine_step_phase_seconds."""
    return {
        (smp.labels["phase"], smp.labels["kind"]): smp.value
        for metric in ENGINE_TELEMETRY_REGISTRY.collect()
        if metric.name == "pst_engine_step_phase_seconds"
        for smp in metric.samples if smp.name.endswith("_count")
    }


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
        finally:
            engine.shutdown()
        return {k: v - before.get(k, 0.0) for k, v in after.items()}

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
    names = {ev[0] for ev in events}
    assert names == {"pst." + p for p in IN_STEP + ("step", "step_info",
                                                    "intake", "no_work")}
    steps = [ev for ev in events if ev[0] == "pst.step"]
    assert len(steps) >= 4  # a prefill step and three decode steps at least
    covered = total = 0.0
    for step in steps:
        inner = _inside(events, step)
        info = [ev[3] for ev in inner if ev[0] == "pst.step_info"]
        assert len(info) == 1, inner
        assert set(info[0]) == {"kind", "bucket", "rows", "new_tokens",
                                "kv_tokens", "kv_pages"}
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
    per_step = grown[("step", "prefill")] + grown[("step", "decode")]
    assert grown[("schedule", "")] == per_step >= steps
    assert grown[("intake", "")] >= per_step + grown[("no_work", "")] - 1
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
    grown = {k: v - before.get(k, 0.0) for k, v in _phase_counts().items()
             if v - before.get(k, 0.0)}
    assert grown == {("step", "decode"): 1, ("launch", "decode"): 1,
                     ("no_work", ""): 1}
    t0 = time.perf_counter()
    for _ in range(2000):
        with ENGINE_TELEMETRY.phase("wait", "decode"):
            pass
    # ten a step must stay far below a 30 ms step; no trace is running
    assert (time.perf_counter() - t0) / 2000 < 200e-6


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
