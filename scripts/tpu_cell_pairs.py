#!/usr/bin/env python3
"""Parent against change on one chip, in one call: `perf/run.py` from two
unpacked copies of the tree in turn (PR 30's recipe, as a script since
PR 40).

    git archive <parent> | tar -x -C .chip_scratch/parent      # then lay
    cp -r BENCHMARK.json perf tests/perf over it, as the driver does
    git add -A; git archive $(git write-tree) | tar -x -C .chip_scratch/change
    chiprun --timeout 3300 -- python3 scripts/tpu_cell_pairs.py <tag> <cell> \\
        change:0:<seed> parent:0:<seed> parent:0:<seed2> change:0:<seed2> \\
        change:1:<seed3> parent:1:<seed3>

A run is ``<side>:<trace>:<seed>``; the sides are directories of
``.chip_scratch/``. Every run's result line goes to
``chiprun_out/<tag>/<cell>.<n>.<side>.t<trace>.json`` with the tail of its
engine log, the harness's log and its ``window.json``, a traced run's
``breakdown`` and, with ``--ops <regex>`` ahead of the tag, the whole text
(operands and shapes) of the device instructions of its capture whose
names match. One JSON row a run is printed as it ends, and the table is
kept. Both sides use one compile cache directory (the one the machine
comes with, else ``.chip_scratch/cache``); where it keeps one
tree's programs only (the larger configurations), put a side's runs next
to each other. Never imports jax in the parent: a process that touched it
would hold the chip."""
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRATCH = os.path.join(ROOT, ".chip_scratch")
SHOWN = ("out_tok_per_s", "itl_p50_ms", "setup_s", "device.idle_share",
         "runner.chain_breaks_on_prefill", "runner.decode_device_step_ms",
         "runner.prefill_device_step_ms", "runner.chained_decode_share",
         "runner.compiles_in_window", "sched.cached_prompt_share",
         "client.itl_p95_ms", "client.ttft_p50_ms", "client.ttft_p95_ms",
         "engine.stall_s", "engine.stall_device_s")


def instructions(profile_dir: str, pattern: str) -> list:
    """[{text, count, total_ms}] of a capture's device instructions whose
    name matches, longest first. Runs in a child with JAX_PLATFORMS=cpu."""
    from jax.profiler import ProfileData

    pat, found = re.compile(pattern), {}
    for path in glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    if pat.search(e.name.split(" = ")[0]):
                        rec = found.setdefault(e.name[:3000], [0, 0.0])
                        rec[0] += 1
                        rec[1] += float(e.duration_ns) / 1e6
    rows = sorted(found.items(), key=lambda kv: -kv[1][1])[:40]
    return [{"text": t, "count": c, "total_ms": ms} for t, (c, ms) in rows]


def main(argv: list) -> int:
    if argv[:1] == ["--instructions"]:  # the child of a traced run
        with open(argv[2], "w") as f:
            json.dump(instructions(argv[1], argv[3]), f, indent=1)
        return 0
    ops = None
    if argv[:1] == ["--ops"]:
        ops, argv = argv[1], argv[2:]
    tag, cell, runs = argv[0], argv[1], argv[2:]
    out_root = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out_root, exist_ok=True)
    env = dict(os.environ)
    # the machine's own cache where it comes with one: the tool keeps it
    # for this repository's next call
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(SCRATCH, "cache"))
    os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    table = []
    for n, spec in enumerate(runs):
        side, trace, seed = spec.split(":")
        cwd = os.path.join(SCRATCH, side)
        run_out = os.path.join(cwd, "perf_out", f"{cell}.{n}")
        t0 = time.time()
        p = subprocess.run(
            [sys.executable, "perf/run.py", "--workload", cell, "--seed", seed,
             "--seconds", "50", "--trace", trace, "--out", run_out],
            cwd=cwd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        name = os.path.join(out_root, f"{cell}.{n}.{side}.t{trace}")
        last = (p.stdout.strip().splitlines() or [""])[-1]
        with open(name + ".json", "w") as f:
            f.write(last + "\n")
        with open(name + ".log.txt", "w") as f:  # the harness's own log
            f.write(p.stdout[-60000:] + "\n--- stderr ---\n" + p.stderr[-10000:])
        if os.path.exists(os.path.join(run_out, "window.json")):
            shutil.copy(os.path.join(run_out, "window.json"),
                        name + ".window.json")
        if os.path.exists(os.path.join(run_out, "engine.log")):
            with open(os.path.join(run_out, "engine.log"), errors="replace") as g:
                tail = g.read()[-60000:]
            with open(name + ".engine.log", "w") as f:
                f.write(tail)
        try:
            line = json.loads(last)
        except ValueError:
            line = {}
        m = line.get("metrics", {})
        row = {"run": os.path.basename(name), "seed": seed, "rc": p.returncode,
               "wall_s": round(time.time() - t0), "correct": line.get("correct"),
               "failed": line.get("failed"), "attempted": line.get("attempted"),
               "n_metrics": len(m), "device": line.get("device")}
        row.update({k: m[k]["value"] for k in SHOWN if k in m})
        row.update({k: v["value"] for k, v in m.items()
                    if "roofline" in k or k.startswith("device.idle_in")})
        table.append(row)
        print(json.dumps(row), flush=True)
        if line.get("breakdown"):
            with open(name + ".breakdown.json", "w") as f:
                json.dump(line["breakdown"], f)
        profile = os.path.join(run_out, "profile")
        if ops and trace == "1" and os.path.isdir(profile):
            q = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--instructions",
                 profile, name + ".instructions.json", ops],
                env=dict(env, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            if q.returncode:
                print("instructions:", q.stdout[-600:], flush=True)
        with open(os.path.join(out_root, f"{cell}.table.json"), "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
