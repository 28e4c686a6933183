#!/usr/bin/env python3
"""What a capture of the profiler costs to stop.

    chiprun -- python scripts/tpu_profile_stop_probe.py \\
        [--config perf/configs/mellum2-ep4-cut.json] [--streams 24] \\
        [--early <variant>:<ms> | --early synth:<events>] \\
        base:3000 served:3000 [...]

``POST /debug/profile`` answers once ``jax.profiler.stop_trace`` has
collected, converted and written the capture; the benchmark's harness waits
120 s for that answer (``perf/run.py::_profile_midway``). This builds the
engine in this process with a configuration of the benchmark (the chip
admits one process), keeps ``--streams`` closed-loop streams decoding, and
takes one capture after another, each under a named set of options
(``VARIANTS``; ``served`` is ``engine/server.py::profile_options_attrs``),
timing start and stop and counting what the written ``.xplane.pb`` holds by
plane, line and program. ``--early`` takes a capture before the streams
start: of the idle engine, or (``synth``) of a loop of small operations
that no step program has. It runs on the chip only and reports the device
it ran on.

What it read on a v5e (PR 46; PERF.md section 6): under live traffic a
process's first capture stops in about 130 us a device event (12.4 s for
0.3 s, 130-136 s for 3 s of a decode step of 4,700 operations at 60 steps a
second) and a later one of no greater length in about 35 us an event,
whatever the options (``hlo_off``, ``host1``, ``xla_only``, first or
later) and whatever was captured before of the idle engine, of another
program, or of the same decode program run back to back on a padding batch.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# name -> ProfileOptions attributes; ``served`` is filled from the server.
VARIANTS = {
    "base": {"python_tracer_level": 0, "host_tracer_level": 2},
    "hlo_off": {"python_tracer_level": 0, "host_tracer_level": 2,
                "enable_hlo_proto": False},
    "host1": {"python_tracer_level": 0, "host_tracer_level": 1},
    "xla_only": {"python_tracer_level": 0, "host_tracer_level": 2,
                 "advanced_configuration": {"tpu_trace_mode": "TRACE_ONLY_XLA"}},
    "compute": {"python_tracer_level": 0, "host_tracer_level": 2,
                "advanced_configuration": {"tpu_trace_mode": "TRACE_COMPUTE"}},
}


def contents(path: str) -> dict:
    """{plane: {line: events}} of a capture, and its size."""
    from jax.profiler import ProfileData

    out = {"bytes": os.path.getsize(path), "planes": {}, "modules": {}}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = [e.name for e in line.events]
            if names:
                lines[line.name] = lines.get(line.name, 0) + len(names)
            if line.name == "XLA Modules":
                for name in names:
                    key = name.split("(")[0]
                    out["modules"][key] = out["modules"].get(key, 0) + 1
        if lines:
            out["planes"][plane.name] = lines
    return out


async def probe(args) -> dict:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs the chip, found {device.platform}; nothing run")

    from perf import config as configs
    from production_stack_tpu.engine import server
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams
    from production_stack_tpu.models import registry

    cfg = configs.load(args.config)
    registry.PRESETS[cfg.name] = configs.program_model_config(cfg)
    eargs = server.parse_engine_args(
        ["--model", cfg.name, "--seed", str(cfg.weights_seed), *cfg.engine_flags])
    engine = AsyncLLMEngine(server.engine_config_from_args(eargs))
    loop = asyncio.get_running_loop()
    engine.start(loop)
    vocab, rng, stop = cfg.hf["vocab_size"], random.Random(46), False

    async def stream():
        while not stop:
            prompt = [rng.randrange(3, vocab) for _ in range(args.prompt)]
            async for _ in engine.generate(
                    prompt_token_ids=prompt,
                    sampling=SamplingParams(max_tokens=args.output,
                                            temperature=0.0, ignore_eos=True)):
                if stop:
                    break

    flight = engine.engine.flight
    report = {"platform": device.platform, "device_kind": device.device_kind,
              "config": cfg.name, "streams": args.streams, "captures": []}

    async def capture(n, spec) -> bool:
        name, ms = spec.split(":")
        options = jax.profiler.ProfileOptions()
        attrs = (server.profile_options_attrs() if name == "served"
                 else VARIANTS[name])
        for key, val in attrs.items():
            setattr(options, key, val)
        out_dir = os.path.join(args.out, f"{os.getpid()}.{n}.{name}")
        os.makedirs(out_dir, exist_ok=True)
        steps0 = flight.stats()["total_steps"]
        t0 = time.perf_counter()
        try:
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(
                    out_dir, profiler_options=options))
        except Exception as e:  # noqa: BLE001 - an option the device refuses
            print(json.dumps({"name": name, "refused": repr(e)}), flush=True)
            return False
        start_s = time.perf_counter() - t0
        await asyncio.sleep(float(ms) / 1e3)
        steps1 = flight.stats()["total_steps"]
        t0 = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        stop_s = time.perf_counter() - t0
        row = {"n": n, "name": name, "ms": float(ms), "options": attrs,
               "start_s": start_s, "stop_s": stop_s,
               "steps_in_capture": steps1 - steps0,
               "steps_while_stopping": flight.stats()["total_steps"] - steps1}
        print(json.dumps(row), flush=True)
        row["dir"] = out_dir
        report["captures"].append(row)
        return True

    if args.early and args.early.startswith("synth:"):
        # a loop of small operations that no step program has: does a
        # capture's first-time cost follow the program or the event count?
        import jax.numpy as jnp

        n = int(args.early.split(":")[1]) // 16

        @jax.jit
        def spin(x):
            def body(_, x):
                for _ in range(16):
                    x = jax.lax.optimization_barrier(x * 1.0001 + 1.0)
                return x
            return jax.lax.fori_loop(0, n, body, x)

        x = jnp.ones((8, 128), jnp.float32)
        spin(x).block_until_ready()
        options = jax.profiler.ProfileOptions()
        for key, val in VARIANTS["base"].items():
            setattr(options, key, val)
        out_dir = os.path.join(args.out, f"{os.getpid()}.synth")
        jax.profiler.start_trace(out_dir, profiler_options=options)
        t0 = time.perf_counter()
        spin(x).block_until_ready()
        ran_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        row = {"n": "early", "name": "synth", "ms": ran_s * 1e3, "options": {},
               "start_s": 0.0, "stop_s": time.perf_counter() - t0,
               "steps_in_capture": 0, "steps_while_stopping": 0}
        print(json.dumps(row), flush=True)
        row["dir"] = out_dir
        report["captures"].append(row)
    elif args.early:  # before any step program is loaded
        await capture("early", args.early)
    streams = asyncio.gather(*(stream() for _ in range(args.streams)))
    while flight.stats()["total_steps"] < args.streams + 300:
        await asyncio.sleep(0.5)
    for n, spec in enumerate(args.captures):
        if not await capture(n, spec):
            break
        await asyncio.sleep(2.0)
    stop = True
    streams.cancel()
    try:
        await streams
    except asyncio.CancelledError:
        pass
    engine.shutdown()
    for row in report["captures"]:
        found = glob.glob(os.path.join(row.pop("dir"), "**", "*.xplane.pb"),
                          recursive=True)
        row["contents"] = contents(found[0]) if found else None
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="perf/configs/mellum2-ep4-cut.json")
    ap.add_argument("--streams", type=int, default=24)
    ap.add_argument("--prompt", type=int, default=3000)
    ap.add_argument("--output", type=int, default=3000)
    ap.add_argument("--out", default=os.path.join(ROOT, "perf_out", "profile_stop"))
    ap.add_argument("--report", default=os.path.join(
        ROOT, "chiprun_out", "profile_stop", "report.json"))
    ap.add_argument("--early", default=None, metavar="<variant>:<ms>",
                    help="a capture before the streams start")
    ap.add_argument("captures", nargs="+", help="<variant>:<milliseconds>")
    args = ap.parse_args(argv)
    report = asyncio.run(probe(args))
    os.makedirs(os.path.dirname(args.report), exist_ok=True)
    with open(args.report, "w") as f:
        json.dump(report, f, indent=1)
    for row in report["captures"]:
        c = row["contents"] or {}
        print(f"{row['name']}:{row['ms']:.0f} stop {row['stop_s']:.1f} s, "
              f"{c.get('bytes', 0) / 1e6:.1f} MB, events by plane: "
              + json.dumps({p: sum(l.values()) for p, l in c.get('planes', {}).items()})
              + " modules: " + json.dumps(c.get("modules", {})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
