"""The engine's own clock for the device (``engine/runner.py``
``_ReadyClock``): each launched program's service time from ready-to-ready
stamps, against scripted launches and polls where every number can be
followed by hand; and the step loop's window account
(``pst_engine_loop_seconds_total``) against the wall of a tiny engine's run.
Nothing here is a device number."""

import asyncio
import time

import pytest

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine import runner
from production_stack_tpu.engine.runner import _ReadyClock
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.obs.engine_telemetry import (
    ENGINE_TELEMETRY,
    LOOP_STATES,
    device_busy_seconds,
    device_idle_seconds,
    device_service_seconds,
    device_step_seconds,
    loop_cycles,
    loop_seconds,
)


class _Program:
    """A launched program's array: ready from ``ends`` on the script's
    clock, counting how often it is asked."""

    def __init__(self, script, name, ends):
        self.script, self.name, self.ends, self.asked = script, name, ends, 0

    def is_ready(self):
        self.asked += 1
        return self.script.now >= self.ends


MS = 1e-3


class _Script:
    """Drives a clock as the runner does, times in milliseconds:
    ``launch`` is a `pst.launch` phase (the head is asked as it opens, the
    program registered at its close), ``fetch`` is `_fetch`'s loop over the
    given poll times."""

    def __init__(self):
        self.now, self.no_work, self.seen, self.programs = 0.0, 0, [], {}
        self.clock = _ReadyClock(self._sink, lambda: self.no_work)

    def _sink(self, entry, start, ready, seen, idle_s, idle_state):
        self.seen.append((entry[1].name, round((ready - start) / MS, 6), seen,
                          round(idle_s / MS, 6), idle_state if idle_s else ""))

    def launch(self, name, opens, closes, ends, kind="decode"):
        self.now = opens * MS
        self.clock.poll(opens * MS)
        self.now = closes * MS
        prog = self.programs[name] = _Program(self, name, ends * MS)
        self.clock.launched(kind, prog, closes * MS, who=name)

    def fetch(self, name, polls):
        own = self.programs[name]
        for at in polls:
            self.now = at * MS
            ready = own.is_ready()
            self.clock.poll(at * MS, own, ready)
            if ready:
                return
        raise AssertionError(f"{name} not ready by {polls[-1]}")


def _chain(s):
    """Every decode step chained: the next is launched, then the one before
    fetched. Each step's time is ready to ready, whatever the host's wall
    around the dispatch call was."""
    s.launch("n", 0, 1, ends=10)
    s.launch("n+1", 2, 3, ends=20)
    s.fetch("n", [3.5, 9, 10])
    s.launch("n+2", 11, 12, ends=30)
    s.fetch("n+1", [12.5, 19.5, 20.5])
    return [("n", 9.0, "poll", 0.0, ""), ("n+1", 10.5, "poll", 0.0, "")]


def _prefill_ahead_of_a_chained_step(s):
    """A prefill launched ahead of the chained step, fetched by nobody here
    (an inner chunk): the fetch of the step behind it asks the prefill too,
    stamps it, and the step's own time is what came after."""
    s.launch("n", 0, 1, ends=10)
    s.fetch("n", [2, 9, 10])
    s.launch("P", 11, 12, ends=50, kind="prefill")
    s.launch("n+1", 12.5, 13, ends=60)
    s.launch("n+2", 14, 15, ends=70)
    s.fetch("n+1", [16, 49, 50, 59, 60])
    # asked as each later launch opens and once a poll until it is ready
    # (one extra is_ready a poll), then never again
    assert s.programs["P"].asked == 5 and runner._FRESH_S == 2.5 * MS
    return [("n", 9.0, "poll", 0.0, ""), ("P", 38.0, "poll", 2.0, "host"),
            ("n+1", 10.0, "poll", 0.0, "")]


def _inner_chunks_nobody_fetches(s):
    """Three chunks of one prompt, the last alone fetched: the two before
    it are seen by its polls, each within one poll of its end."""
    s.launch("c1", 0, 1, ends=30, kind="prefill")
    s.launch("c2", 1.5, 2, ends=55, kind="prefill")
    s.launch("c3", 2.5, 3, ends=80, kind="prefill")
    s.fetch("c3", [3.5, 29, 30, 54, 56, 79, 80])
    return [("c1", 29.0, "poll", 0.0, ""), ("c2", 26.0, "poll", 0.0, ""),
            ("c3", 24.0, "poll", 0.0, "")]


def _idle_with_and_without_no_work(s):
    """Between a ready and a later launch the device is idle: the loop's
    wait for work in between files it under no_work, else under host."""
    s.launch("a", 0, 1, ends=10)
    s.fetch("a", [2, 9, 10])
    s.no_work += 2
    s.launch("b", 99, 100, ends=110)
    s.fetch("b", [101, 109, 110])
    s.launch("c", 129, 130, ends=140)
    s.fetch("c", [131, 139, 140])
    return [("a", 9.0, "poll", 0.0, ""), ("b", 10.0, "poll", 90.0, "no_work"),
            ("c", 10.0, "poll", 20.0, "host")]


def _seen_late(s):
    """A program found ready with no ask just before that found it running
    ended at some moment since: its interval holds what the host was late
    by, and says so. The first poll that asks (a); a launch's opening poll,
    which stamps what ended while the host was busy, so that the time to
    that launch is idle and not service (b); a fetch's first poll after the
    host's own phases, 3 ms after the launch's opening had found it running
    (d); and a poll that came back late, the thread not let run (e)."""
    s.launch("a", 0, 1, ends=5)
    s.fetch("a", [8])
    s.launch("b", 9, 10, ends=12)
    s.launch("c", 20, 21, ends=31)  # its launch finds b ready
    s.fetch("c", [22, 30, 31])
    s.launch("d", 32, 33, ends=43)
    s.launch("e", 41, 44, ends=54)  # d still runs as this opens, and ends
    s.fetch("d", [44.1])            # before its close
    s.fetch("e", [45, 46, 160])
    return [("a", 7.0, "late", 0.0, ""), ("b", 10.0, "late", 2.0, "host"),
            ("c", 10.0, "poll", 1.0, "host"), ("d", 11.1, "late", 2.0, "host"),
            ("e", 115.9, "late", 0.0, "")]


def _ready_together(s):
    """Own array ready: everything launched before it is done too and is
    not asked; both take the poll's stamp."""
    s.launch("P", 0, 1, ends=4, kind="prefill")
    s.launch("n", 1.5, 2, ends=4.5)
    s.fetch("n", [3, 5])  # both asked at 3, 2 ms before
    assert s.programs["P"].asked == 2  # at 1.5 and at 3, not at 5
    return [("P", 4.0, "poll", 0.0, ""), ("n", 0.0, "poll", 0.0, "")]


@pytest.mark.parametrize("script", [
    _chain, _prefill_ahead_of_a_chained_step, _inner_chunks_nobody_fetches,
    _idle_with_and_without_no_work, _seen_late, _ready_together,
], ids=lambda f: f.__name__.strip("_"))
def test_the_clock_against_scripted_launches_and_polls(script):
    s = _Script()
    assert s.seen == script(s)
    # errors telescope: service and idle sum to last ready less first start
    first_start = 1.0  # every script's first launch closes at 1
    total = sum(x[1] + x[3] for x in s.seen)
    assert total == pytest.approx(s.clock._ready_at / MS - first_start)


def test_a_program_of_no_live_traffic_moves_the_clock_and_no_counter():
    ENGINE_TELEMETRY.reset_for_tests()
    busy0 = ENGINE_TELEMETRY.device_busy_seconds()
    ENGINE_TELEMETRY.record_ready("decode", "b8", 1.0, 1.0, 2.0, "poll", live=False)
    assert ENGINE_TELEMETRY.device_busy_seconds() == busy0
    idle0 = device_idle_seconds.labels(state="host")._value.get()
    late0 = device_service_seconds.labels(
        kind="decode", seen="late")._value.get()
    hist = device_step_seconds.labels(kind="decode")
    n0 = hist._sum.get()
    ENGINE_TELEMETRY.record_ready("decode", "b8", 1.0, 1.5, 2.0, "late", 0.25, "host")
    ENGINE_TELEMETRY.record_ready("decode", "b8", 2.0, 2.0, 2.25, "poll")
    assert ENGINE_TELEMETRY.device_busy_seconds() - busy0 == pytest.approx(0.75)
    assert device_idle_seconds.labels(
        state="host")._value.get() - idle0 == pytest.approx(0.25)
    assert device_service_seconds.labels(
        kind="decode", seen="late")._value.get() - late0 == pytest.approx(0.5)
    assert hist._sum.get() - n0 == pytest.approx(0.25)  # the late one is not in it


def _loop_total():
    return sum(loop_seconds.labels(state=st)._value.get() for st in LOOP_STATES)


def _edge():
    """(wall, the account's sum) the moment a stretch has just been counted."""
    before = _loop_total()
    while True:
        now, total = time.perf_counter(), _loop_total()
        if total != before:
            return now, total
        time.sleep(0.0002)


async def test_the_loop_states_sum_to_the_wall_of_a_tiny_engines_run():
    """Cycles begin where the last stretch ended, a cycle or a wait for
    work: between two moments at which a stretch was just counted, the
    states' changes are the wall, to 1 %."""
    ENGINE_TELEMETRY.reset_for_tests()
    engine = AsyncLLMEngine(EngineConfig(
        model="tiny-llama-debug", max_model_len=256, block_size=16,
        num_kv_blocks=128, max_num_seqs=8, cost_attribution=True))
    engine.start(asyncio.get_event_loop())
    try:
        async def one(i):
            async for _ in engine.generate(
                    prompt=f"question {i} " * (i % 5 + 1), request_id=f"r{i}",
                    sampling=SamplingParams(max_tokens=24, temperature=0.0)):
                pass

        await asyncio.gather(*(one(i) for i in range(3)))  # compiles
        await asyncio.sleep(0.12)
        cycles0 = {st: loop_cycles.labels(state=st)._value.get()
                   for st in LOOP_STATES}
        busy0 = device_busy_seconds._value.get()
        idle0 = sum(device_idle_seconds.labels(state=st)._value.get()
                    for st in ("host", "no_work"))
        t0, sum0 = await asyncio.to_thread(_edge)
        await asyncio.gather(*(one(10 + i) for i in range(4)))
        await asyncio.sleep(0.3)  # and the loop waits for work again
        await asyncio.gather(*(one(20 + i) for i in range(2)))
        await asyncio.sleep(0.12)
        t1, sum1 = await asyncio.to_thread(_edge)
    finally:
        engine.shutdown()
    wall = t1 - t0
    assert wall > 0.5
    assert sum1 - sum0 == pytest.approx(wall, rel=0.01)
    moved = {st: loop_cycles.labels(state=st)._value.get() - cycles0[st]
             for st in LOOP_STATES}
    assert moved["decode"] > 0 and moved["prefill"] > 0 and moved["no_work"] >= 5
    # the device's side of the same stretch: busy and idle are inside it
    busy = device_busy_seconds._value.get() - busy0
    idle = sum(device_idle_seconds.labels(state=st)._value.get()
               for st in ("host", "no_work")) - idle0
    assert 0 < busy < wall and 0 < idle < wall + 0.2
