#!/usr/bin/env python3
"""One run of a benchmark cell with per-layer metrics that wait to be
entries of ``BENCHMARK.json`` laid over the accepted benchmark.

    chiprun --timeout 1800 -- python3 scripts/tpu_cell_extra.py \\
        tests/perf/data/BENCHMARK.looped.json <cell> <seed> <trace 0|1> [<out dir>]

The first argument is a file of entries in the shape of
``tests/perf/data/BENCHMARK.later.json`` (lists to append, by the
benchmark's own keys); the metrics' files are looked for in
``tests/perf/data/layer_metrics/`` before ``perf/layer_metrics/``. Everything
else is ``perf/run.py``'s: the same harness, traffic, check and readers,
through the door its tests use (``run_cell(bench=..., data_dirs=...)``).
Prints the result line; never imports jax in this process."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf import manifest, run  # noqa: E402
from perf.harness import BenchError  # noqa: E402


def main(argv: list) -> int:
    extra, cell, seed, trace = argv[0], argv[1], int(argv[2]), bool(int(argv[3]))
    out_dir = argv[4] if len(argv) > 4 else None
    with open(os.path.join(ROOT, extra)) as f:
        more = json.load(f)
    bench = manifest.load()
    bench = dict(bench, **{group: bench[group] + entries
                           for group, entries in more.items()
                           if not group.startswith("_")})
    dirs = {"layer_metrics": [os.path.join(ROOT, "tests", "perf", "data",
                                           "layer_metrics")]}
    try:
        result = run.run_cell(cell, seed, 50.0, trace, out_dir=out_dir,
                              bench=bench, data_dirs=dirs)
    except BenchError as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
