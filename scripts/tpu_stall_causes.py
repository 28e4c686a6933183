#!/usr/bin/env python3
"""Each cause of a stall provoked once, and what the telemetry costs.

    chiprun -- python scripts/tpu_stall_causes.py provoke [--out DIR]
    chiprun -- python scripts/tpu_stall_causes.py cost

``provoke`` builds the engine in this process with the dense configuration
of the benchmark (``perf/configs/mistral-7b-int4.json``; the chip admits one
process, so what disturbs the engine has to live beside it), drives four
closed-loop streams through it and, once the decode bucket's bar is armed,
disturbs it five ways, one at a time:

- ``machine``: a helper process sends this one SIGSTOP, and SIGCONT 1 s later;
- ``gc``: a full collection over a large graph, from another thread;
- ``interpreter``: another thread in one long native call that keeps the
  interpreter lock (sorting a large list);
- ``device``: another thread enqueues a jitted program of about 1 s on the
  chip ahead of the step's;
- ``compile``: a prompt of a length whose prefill shape was not met before.

For each it reports what the disturbance left: the WARNING lines of the
engine's log, the growth of ``pst_engine_stalls_total`` and
``pst_engine_stall_seconds_total`` by cause, and the ``detail`` of the flight
recorder's new snapshots. Nothing of this is in the program, and the engine
has no hook for it. (``host_work`` is shown in the CPU tests.) It runs on
the chip only: off it the script says so and exits 1, and the report names
the device it ran on.

``cost`` times the always-on parts in a loop on this machine's host, no
chip needed: one phase's enter + exit, one poll of ``_fetch``, and one whole
cycle of the step loop as the telemetry sees it (intake, step, five phases,
a dispatch, at 83 dispatches a second of a faked clock so that the throughput
window holds what it holds in a cell), and the walk over that window alone.
It uses nothing that PR 36 added, so that the same file runs in the parent's
tree.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import logging
import os
import random
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "perf/configs/mistral-7b-int4.json"
STREAMS = 4
BUSY_ITERS = 1400  # 4096^3 products of the program that holds the chip 1 s
CYCLES = 20000
_STOPPER = (
    "import os, signal, sys, time; pid = int(sys.argv[1]); time.sleep(0.3); "
    "os.kill(pid, signal.SIGSTOP); time.sleep(1.0); os.kill(pid, signal.SIGCONT)"
)


# -- cost ---------------------------------------------------------------


def _ns_each(fn, n: int) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e9


def cost(cycles: int = CYCLES) -> dict:
    import numpy as np

    from production_stack_tpu.engine import runner
    from production_stack_tpu.obs import ENGINE_TELEMETRY as tel
    from production_stack_tpu.obs.flight import FlightRecorder

    rec = FlightRecorder(512)
    rec.set_probe(lambda: {"waiting": 0, "running": 16, "swapped": 0,
                           "batch_tier_rows": 0, "kv_occupancy": 0.5,
                           "preemptions": 0})
    tel.attach_flight(rec)

    def phase():
        with tel.phase("launch", "decode"):
            pass

    def wait_phase():  # the one phase that reads the thread's CPU clock
        with tel.phase("wait", "decode"):
            pass

    class Ready:
        """Ready at the 41st poll, as a decode step's fetch is."""

        def __init__(self):
            self.asked = 0

        def copy_to_host_async(self):
            pass

        def is_ready(self):
            self.asked += 1
            return self.asked > 40

        def __array__(self, dtype=None, copy=None):
            return value

    value = np.zeros(3, np.int32)
    key = ("cost", "decode", ("b16xn1",))
    clock = [1000.0]
    # The runner's clock for the device (PR 51; a tree before it has none,
    # and this script runs there too): each cycle asks the head as its
    # launch opens, registers the program at the launch's close, and the
    # fetch's last poll sees the one before ready: a stamp, the counters,
    # the histogram, a pst.ready span and the cycle's record.
    ready = getattr(runner, "_ReadyClock", None)
    if ready is not None:
        ready = ready(lambda e, start, at, seen, idle_s, state: tel.record_ready(
            e[0], "b16xn1", e[2], start, at, seen, idle_s, state))
    in_flight = []

    def tick():
        clock[0] += 0.012
        return clock[0]

    def cycle():
        with tel.phase("intake"):
            pass
        with tel.phase("step"):
            with tel.phase("schedule"):
                pass
            with tel.phase("batch_build", "decode"):
                tel.step_info("decode", bucket="b16xn1", rows=16,
                              new_tokens=16, kv_tokens=100_000, kv_pages=800)
            with tel.phase("launch", "decode", pipelined=1) as launch:
                if ready is not None:
                    ready.poll(launch.t0)
            if ready is not None:
                in_flight.append(Ready())
                ready.launched("decode", in_flight[-1], launch.t1, "who")
            with tel.phase("wait", "decode") as wait:
                if len(in_flight) > 1:
                    ready.poll(wait.t0, in_flight.pop(0), True)
            tel.record_host_gap("b16xn1", 0.0)
            with tel.phase("postprocess", "decode"):
                tel.record_dispatch("decode", key, 0.012, batch_bucket="b16xn1",
                                    tokens=16, fill_ratio=1.0)
            with tel.phase("postprocess", "decode"):
                pass

    out = {"phase_ns": _ns_each(phase, cycles * 5),
           "wait_phase_ns": _ns_each(wait_phase, cycles * 5)}
    real_sleep, real_monotonic = time.sleep, time.monotonic
    try:
        time.sleep = lambda s: None  # the poll's own work, not its 0.3 ms
        def fetch():
            if ready is None:
                return runner._fetch(Ready(), "decode")
            own = Ready()
            ready.launched("decode", own, time.perf_counter())
            return runner._fetch(own, "decode", ready)

        fetch_ns = _ns_each(fetch, cycles // 10)
        out["fetch_of_40_polls_ns"] = fetch_ns
        time.monotonic = tick
        for _ in range(1000):  # the throughput window fills: 833 samples
            cycle()
        out["cycle_ns"] = _ns_each(cycle, cycles)
    finally:
        time.sleep, time.monotonic = real_sleep, real_monotonic
    def walk():  # a dispatch paid it before PR 36, a /metrics scrape since
        with tel._lock:
            tel._refresh_throughput_locked(clock[0])

    out["throughput_walk_ns"] = _ns_each(walk, cycles // 10)
    no_poll = _ns_each(lambda: runner._fetch(value_ready, "decode"), cycles // 10)
    out["poll_ns"] = (fetch_ns - no_poll) / 40
    out["cycle_with_40_polls_ns"] = out["cycle_ns"] + out["poll_ns"] * 40
    out["throughput_samples_held"] = len(tel._tok_samples)
    return out


class _AlwaysReady:
    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        return np.zeros(3, np.int32)


value_ready = _AlwaysReady()


# -- provoke ------------------------------------------------------------


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def _stall_counters() -> dict:
    from production_stack_tpu.obs import ENGINE_TELEMETRY_REGISTRY

    return {
        f"{smp.name}{{{smp.labels.get('cause', '')}}}": smp.value
        for metric in ENGINE_TELEMETRY_REGISTRY.collect()
        if metric.name in ("pst_engine_stalls", "pst_engine_stall_seconds",
                           "pst_engine_gc_pause_seconds")
        for smp in metric.samples if smp.name.endswith("_total")
    }


async def provoke() -> dict:
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"provoke: needs the chip, found {device.platform} "
            f"({device.device_kind}); nothing run")

    from perf import config as configs
    from production_stack_tpu.engine import server
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams
    from production_stack_tpu.models import registry

    cfg = configs.load(CONFIG)
    registry.PRESETS[cfg.name] = configs.program_model_config(cfg)
    args = server.parse_engine_args(
        ["--model", cfg.name, "--seed", str(cfg.weights_seed), *cfg.engine_flags])
    lines = _Lines()
    logging.getLogger("production_stack_tpu.obs.engine_telemetry").addHandler(lines)

    # What the disturbances need, made before the engine serves: building
    # them takes the interpreter lock too.
    rng = random.Random(36)
    graph = [[i] for i in range(6_000_000)]
    unsorted = [rng.random() for _ in range(2_500_000)]
    gc.collect()
    gc.freeze()  # the engine's own collections walk none of it

    engine = AsyncLLMEngine(server.engine_config_from_args(args))
    loop = asyncio.get_running_loop()
    engine.start(loop)
    vocab = cfg.hf["vocab_size"]

    @jax.jit
    def busy(x, w):
        return jax.lax.fori_loop(
            0, BUSY_ITERS, lambda _, y: (y @ w).astype(y.dtype), x)

    x = jnp.ones((4096, 4096), jnp.bfloat16) * 0.01
    w = jnp.eye(4096, dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    busy(x, w).block_until_ready()  # compiles
    t0 = time.perf_counter()
    busy(x, w).block_until_ready()
    busy_s = time.perf_counter() - t0

    stop = False

    async def generate(n_prompt: int, n_out: int):
        prompt = [rng.randrange(3, vocab) for _ in range(n_prompt)]
        async for _ in engine.generate(
                prompt_token_ids=prompt,
                sampling=SamplingParams(max_tokens=n_out, temperature=0.0,
                                        ignore_eos=True)):
            pass

    async def stream():
        while not stop:
            await generate(48, 96)

    streams = asyncio.gather(*(stream() for _ in range(STREAMS)))
    flight = engine.engine.flight
    while flight.stats()["total_steps"] < 600:  # every bucket's bar is armed
        await asyncio.sleep(0.2)
    warm_lines = len(lines.lines)
    report = {"platform": device.platform, "device_kind": device.device_kind,
              "busy_program_s": busy_s, "warmup_stall_lines": lines.lines[:],
              "causes": {}}

    def unfrozen_collect():
        gc.unfreeze()
        t = time.perf_counter()
        gc.collect()
        dt = time.perf_counter() - t
        gc.freeze()
        return dt

    def stop_and_continue():
        subprocess.run([sys.executable, "-c", _STOPPER, str(os.getpid())],
                       check=True)

    disturbances = [
        ("machine", lambda: loop.run_in_executor(None, stop_and_continue)),
        ("gc", lambda: loop.run_in_executor(None, unfrozen_collect)),
        ("interpreter", lambda: loop.run_in_executor(None, sorted, unsorted)),
        ("device", lambda: loop.run_in_executor(
            None, lambda: busy(x, w).block_until_ready())),
        ("compile", lambda: generate(700, 4)),
    ]
    for cause, disturb in disturbances:
        await asyncio.sleep(2.0)
        before, n_lines, t_mark = _stall_counters(), len(lines.lines), time.time()
        t = time.perf_counter()
        await disturb()
        took = time.perf_counter() - t
        await asyncio.sleep(2.0)
        grown = {k: round(v - before.get(k, 0.0), 6)
                 for k, v in _stall_counters().items() if v != before.get(k, 0.0)}
        details = [s["detail"] for s in flight.snapshots() if s["ts"] >= t_mark]
        report["causes"][cause] = {
            "disturbance_s": round(took, 3),
            "named": sorted({d["cause"] for d in details}),
            "named_rightly": any(d["cause"] == cause for d in details)
            and grown.get(f"pst_engine_stalls_total{{{cause}}}", 0) >= 1
            and any(f": {cause} (" in ln for ln in lines.lines[n_lines:]),
            "log": lines.lines[n_lines:],
            "counters": grown,
            "snapshots": details,
        }
    stop = True
    streams.cancel()
    try:
        await streams
    except asyncio.CancelledError:
        pass
    polls = [r for r in flight.records() if r["kind"] == "decode"
             and r["polls"] and r["wait_s"]]
    report["poll_pace"] = {
        "decode_cycles": len(polls),
        "polls_mean": sum(r["polls"] for r in polls) / max(len(polls), 1),
        "mean_gap_ms": 1e3 * sum(r["wait_s"] for r in polls)
        / max(sum(r["polls"] for r in polls), 1),
        "longest_gap_ms_median": 1e3 * sorted(
            r["poll_gap_max_s"] for r in polls)[len(polls) // 2] if polls else None,
    }
    report["lines_after_warmup"] = len(lines.lines) - warm_lines
    engine.shutdown()
    del graph
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("provoke", "cost"))
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "stall_causes"))
    args = ap.parse_args(argv)
    if args.what == "cost":
        print(json.dumps(cost()))
        return 0
    report = asyncio.run(provoke())
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    for cause, got in report["causes"].items():
        print(f"{cause}: named {got['named']} rightly={got['named_rightly']} "
              f"counters {got['counters']}")
        for ln in got["log"]:
            print("   ", ln)
    print(json.dumps({k: report[k] for k in (
        "platform", "device_kind", "poll_pace", "busy_program_s")}))
    return 0 if all(g["named_rightly"] for g in report["causes"].values()) else 1


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
