#!/usr/bin/env python3
"""After ``perf/calibrate.py`` has served a cell's check sets and kept the
parsed responses: the reference's further negative controls over the same
responses, one reference child for all of them.

    chiprun -- python scripts/tpu_calibrate_variants.py <calibrate --out dir> \
        <cell> <variant>[:<seeds>] [<variant>[:<seeds>] ...]

Prints, per variant and seed, what ``perf/check.py`` compares (share of clear
positions within tau, median and largest error) under the thresholds of the
configuration file, and whether the seed would be ``correct``. ``:<seeds>``
takes the first so many seeds only (a control that fails five times over
needs no more); variants over the same seeds share a child. The reference's
numbers stay in ``<dir>/variants<seeds>/`` for a closer look.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import check, config as configs, manifest  # noqa: E402
from perf import run as runmod  # noqa: E402


def main(argv) -> int:
    out_dir, cell_name = argv[0], argv[1]
    cell = manifest.cell(manifest.load(), cell_name)
    cfg = configs.load(cell["config_file"])
    with open(os.path.join(out_dir, "calib_parsed.json")) as f:
        all_seeds = json.load(f)
    by_seeds = {}
    for arg in argv[2:]:
        variant, _, n = arg.partition(":")
        by_seeds.setdefault(int(n) if n else len(all_seeds), []).append(variant)
    for n, variants in by_seeds.items():
        per_seed = dict(list(all_seeds.items())[:n])
        ref_dir = os.path.join(
            out_dir, "variants" + ("" if n == len(all_seeds) else str(n)))
        os.makedirs(ref_dir, exist_ok=True)
        reference = runmod.reference_of(
            cfg, [p for ps in per_seed.values() for p in ps], variants,
            ref_dir, 3000)
        for variant in variants:
            rows = []
            for seed, parsed in per_seed.items():
                v = check.compare(
                    parsed, reference["variants"][variant], cfg.check)
                rows.append({"seed": seed, **{k: v[k] for k in (
                    "correct", "clear_share", "clear_within_tau",
                    "median_clear_err", "max_clear_err", "max_unclear_err")}})
            print(json.dumps({"variant": variant, "thresholds": cfg.check,
                              "correct_on": sum(r["correct"] for r in rows),
                              "seeds": len(rows), "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
