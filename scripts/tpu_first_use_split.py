#!/usr/bin/env python3
"""What a step shape's first use in a process costs, by part: one cell's
engine started several times in fresh processes against one cache
directory (a cold start, then warm ones), each walking the same step shapes
once through the runner's warm-up dispatches.

    chiprun --timeout 3000 -- python3 scripts/tpu_first_use_split.py \\
        [--tag x] [--starts cold,warm,warm] [--no-store] [--fresh-cache] \\
        [--rows 4] [--min-tokens 16] <cell>

A start prints one row a shape (family, label, table width, flags, wall and
the split of `pst_engine_program_first_use_seconds_total{phase}` over it,
the program store's outcome), the sums by family, the store's outcomes and
its size on disk. ``--no-store`` walks the same shapes with the store taken
out (every first use traced, as before the store). The shapes are the
cell's chained and burst decode programs and its prefill programs of up to
``--rows`` rows and at least ``--min-tokens`` tokens, both sampling
variants: about what a benchmark run's probes and histories meet. The cache
is the directory the machine comes with (``JAX_COMPILATION_CACHE_DIR``),
else, and with ``--fresh-cache`` always, ``.chip_scratch/first_use_cache``
(emptied by ``--fresh-cache``, else kept from one invocation to the next).
A program built for the store is compiled past XLA's cache, so a start cold
for the store compiles every shape whatever XLA holds; with ``--no-store``
and the machine's cache XLA may hold the programs already. Everything is kept under ``chiprun_out/<tag>/``. The
parent never imports jax."""
import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PHASES = ("trace", "lower", "backend_compile", "cache_read", "load", "write",
          "other")
OUTCOMES = ("loaded", "built", "rejected")


def _counters() -> dict:
    from production_stack_tpu.obs import ENGINE_TELEMETRY_REGISTRY as reg

    out = {p: reg.get_sample_value(
        "pst_engine_program_first_use_seconds_total", {"phase": p}) or 0.0
        for p in PHASES}
    out.update({o: reg.get_sample_value(
        "pst_engine_program_store_total", {"outcome": o}) or 0.0
        for o in OUTCOMES})
    return out


def _dir_size(path: str) -> tuple:
    n = size = 0
    for folder, _, files in os.walk(path):
        for f in files:
            n += 1
            size += os.path.getsize(os.path.join(folder, f))
    return n, size


def child(args) -> int:
    t_start = time.time()
    from perf import config as configs, manifest

    cell = manifest.cell(manifest.load(args.bench), args.cell)
    cfg = configs.load(cell["config_file"])
    from production_stack_tpu.models import registry

    registry.PRESETS[cfg.name] = configs.program_model_config(cfg)
    from production_stack_tpu.engine import runner as runner_mod, server
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.precompile import enumerate_lattice

    if args.no_store:
        runner_mod.open_store = lambda *a, **k: None
    engine_cfg = server.engine_config_from_args(server.parse_engine_args([
        "--model", cfg.name, "--seed", str(cfg.weights_seed),
        # the variable places the cache; the flag says that the engine's own
        # configuration placed it, which a CPU rehearsal's store asks for
        "--compile-cache-dir", os.environ["JAX_COMPILATION_CACHE_DIR"],
        *cfg.engine_flags]))
    engine = LLMEngine(engine_cfg)
    runner = engine.runner
    import jax

    ready_s = time.time() - t_start
    shapes = [b for b in enumerate_lattice(engine_cfg)
              if not b.want_lp and not b.penalized and (
                  b.kind == "decode_burst" or (
                      b.kind == "prefill" and b.rows <= args.rows
                      and b.tokens >= args.min_tokens))]
    rows, by_family = [], {}
    for b in shapes:
        before, t0 = _counters(), time.perf_counter()
        runner.warmup_bucket(b)
        wall = time.perf_counter() - t0
        after = _counters()
        row = {"family": b.kind, "label": b.label, "width": b.width,
               "greedy": b.greedy, "wall_s": round(wall, 4)}
        row.update({p: round(after[p] - before[p], 4) for p in PHASES})
        row["outcome"] = ",".join(
            f"{o}:{after[o] - before[o]:.0f}" for o in OUTCOMES
            if after[o] != before[o])
        rows.append(row)
        print(json.dumps(row), flush=True)
        fam = by_family.setdefault(b.kind, dict.fromkeys(
            ("shapes", "wall_s", *PHASES), 0.0))
        fam["shapes"] += 1
        fam["wall_s"] += wall
        for p in PHASES:
            fam[p] += row[p]
    store = runner.programs.store
    total = _counters()
    summary = {
        "cell": args.cell, "start": args.start, "no_store": args.no_store,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
        "engine_built_s": round(ready_s, 2),
        "shapes": len(shapes),
        "first_use_s": round(sum(total[p] for p in PHASES), 3),
        "phases": {p: round(total[p], 3) for p in PHASES},
        "outcomes": {o: total[o] for o in OUTCOMES},
        "by_family": {k: {kk: round(vv, 3) for kk, vv in v.items()}
                      for k, v in by_family.items()},
        "store": store.path if store else None,
        "store_entries_bytes": _dir_size(store.path) if store else None,
        "cache_dir": runner.device_info.get("compile_cache_dir"),
        "process_s": round(time.time() - t_start, 2),
    }
    print("SUMMARY " + json.dumps(summary), flush=True)
    with open(args.out, "w") as f:
        json.dump({"summary": summary, "rows": rows}, f, indent=1)
    return 0


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("--tag", default="first_use")
    p.add_argument("--starts", default="cold,warm,warm")
    p.add_argument("--no-store", action="store_true")
    p.add_argument("--fresh-cache", action="store_true")
    p.add_argument("--rows", type=int, default=4)
    p.add_argument("--min-tokens", type=int, default=16)
    p.add_argument("--bench", default=None,
                   help="another BENCHMARK.json (a CPU rehearsal's tiny one)")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--start", default="", help=argparse.SUPPRESS)
    p.add_argument("--out", default="", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child:
        return child(args)
    out_root = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_root, exist_ok=True)
    env = dict(os.environ)
    if args.fresh_cache or not env.get("JAX_COMPILATION_CACHE_DIR"):
        cache = os.path.join(ROOT, ".chip_scratch", "first_use_cache")
        if args.fresh_cache:
            shutil.rmtree(cache, ignore_errors=True)
        os.makedirs(cache, exist_ok=True)
        env["JAX_COMPILATION_CACHE_DIR"] = cache
    print(f"cache directory: {env['JAX_COMPILATION_CACHE_DIR']}", flush=True)
    rc = 0
    for n, start in enumerate(args.starts.split(",")):
        name = (f"{args.cell}.{n}.{start}"
                + (".no_store" if args.no_store else ""))
        out = os.path.join(out_root, name + ".json")
        cmd = [sys.executable, os.path.abspath(__file__), args.cell,
               "--child", "--start", start, "--out", out,
               "--rows", str(args.rows), "--min-tokens", str(args.min_tokens)]
        if args.no_store:
            cmd.append("--no-store")
        if args.bench:
            cmd += ["--bench", args.bench]
        t0 = time.time()
        q = subprocess.run(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                           stderr=subprocess.PIPE, text=True)
        with open(os.path.join(out_root, name + ".log.txt"), "w") as f:
            f.write(q.stdout[-200000:] + "\n--- stderr ---\n"
                    + q.stderr[-60000:])
        line = [ln for ln in q.stdout.splitlines() if ln.startswith("SUMMARY ")]
        print(f"== {name}: rc {q.returncode}, {time.time() - t0:.0f} s",
              flush=True)
        print(line[-1] if line else q.stderr[-3000:], flush=True)
        rc = rc or q.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
