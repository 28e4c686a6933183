#!/usr/bin/env python3
"""Calibrate ``correct`` on the chip, before any timed run.

    python perf/calibrate.py --workload <cell> --seeds 16 [--negative <variant>]
        [--out <dir>]

With the engine up once, sends the cell's check set for ``--seeds`` seeds,
then hands every sequence to one reference child (``none`` and the negative
control's variant, one of the ``VARIANTS`` of the configuration's own
reference module: the child refuses any other and names them) and prints,
per seed, the share of clear positions and the largest clear / unclear
error, for a sweep of ``delta``. The thresholds
in the configuration file are set from this table; the parsed responses and
the reference's numbers stay in ``--out`` for a closer look.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import check, client, config as configs, manifest  # noqa: E402
from perf import run as runmod  # noqa: E402
from perf.harness import log  # noqa: E402

DELTAS = (0.0, 0.01, 0.02, 0.03, 0.05, 0.08, 0.12)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--negative", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    cfg = configs.load(cell["config_file"])
    mix = manifest.load_mix(cell["traffic"])
    out_dir = args.out or os.path.join(ROOT, "perf_out", "calibrate", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    gen = importlib.import_module(f"perf.generators.{mix['generator']}")
    vocab = cfg.hf["vocab_size"]
    engine = runmod.Engine(cfg, out_dir, profiling=False)
    per_seed = {}
    try:
        dev = engine.wait_ready()
        log(f"engine ready; device path {json.dumps(dev)}")
        runmod._expect_device(dev, cfg, cell["chips"], manifest.load_peaks())
        engine.prove_tokenizer()
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            plan = gen.plan(mix, seed, 4.0, vocab)
            sessions = []
            if plan["sessions"]:  # only the session the check will pick
                sessions = [client.Session(min(plan["sessions"], key=len))]
            runmod.prefill_contexts(
                engine, plan["setup_prompts"] + [s.tokens for s in sessions])
            seqs = check.check_set(mix, plan, sessions, seed, vocab)
            parsed = [check.parse_response(
                s, engine.complete(check.request_body(cfg.name, s["prompt"])))
                for s in seqs]
            for p in parsed:
                p["id"] = f"{seed}.{p['id']}"
            per_seed[seed] = parsed
            log(f"seed {seed}: {[len(p['tokens']) for p in parsed]} tokens, "
                f"complete {[p['complete'] for p in parsed]}")
        log("memory: " + json.dumps(engine.memory()))
    finally:
        engine.stop()

    with open(os.path.join(out_dir, "calib_parsed.json"), "w") as f:
        json.dump({str(k): v for k, v in per_seed.items()}, f)
    variants = ["none"] + ([args.negative] if args.negative else [])
    reference = runmod.reference_of(
        cfg, [p for ps in per_seed.values() for p in ps], variants, out_dir,
        3000)
    log(f"reference took {reference['seconds']:.1f}s on {reference['platform']}")
    tau = float(cfg.check["tau"])
    table = []
    for variant in variants:
        for delta in DELTAS:
            for seed, parsed in per_seed.items():
                v = check.compare(parsed, reference["variants"][variant],
                                  {"delta": delta, "tau": tau,
                                   "tau_loose": float("inf"), "clear_within_min": 0})
                table.append({"variant": variant, "delta": delta, "seed": seed,
                              **{k: v[k] for k in (
                                  "clear_share", "clear_within_tau", "median_clear_err",
                                  "max_clear_err", "max_unclear_err")},
                              "incomplete": len(v["incomplete"])})
    with open(os.path.join(out_dir, "calib_table.json"), "w") as f:
        json.dump(table, f)
    for variant in variants:
        print(f"== variant {variant}; over {len(per_seed)} seeds, tau {tau}")
        for delta in DELTAS:
            rows = [r for r in table if r["variant"] == variant and r["delta"] == delta]
            lo = lambda k: min(r[k] for r in rows)  # noqa: E731
            hi = lambda k: max(r[k] for r in rows)  # noqa: E731
            print(f"delta {delta:5.2f}: clear share {lo('clear_share'):.3f}-{hi('clear_share'):.3f}"
                  f"  clear within tau {lo('clear_within_tau'):.3f}-{hi('clear_within_tau'):.3f}"
                  f"  median clear err {lo('median_clear_err'):.4f}-{hi('median_clear_err'):.4f}"
                  f"  max clear err {lo('max_clear_err'):.4f}-{hi('max_clear_err'):.4f}"
                  f"  max unclear err {hi('max_unclear_err'):.4f}"
                  f"  incomplete {sum(r['incomplete'] for r in rows)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
