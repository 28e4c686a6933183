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
Prints the result line; never imports jax in this process.

Traced or not, the run's last two scrapes (the window's ends) also give the
**window account**, one line on standard error and ``window_account.json``
in the run's directory (:func:`window_account`): the step thread's wall by
what the loop was doing (``pst_engine_loop_seconds_total``: the states sum
to the wall between the scrapes), the cycles of each state, the device's
busy and idle seconds by the engine's own clock, the stalls by cause and
the mean service time of a decode and a prefill program. A run that reads
5-10 % low says there whether it made fewer decode cycles because seconds
went to ``no_work`` (the clients were late), to ``prefill`` (the mix), to
stalls, or because the device's own step was slower."""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf import manifest, run  # noqa: E402
from perf.harness import BenchError  # noqa: E402


def _by_label(before: dict, after: dict, name: str, label: str,
              **where) -> dict:
    """{label's value: the counter's change between the two scrapes}, over
    the series whose other labels are ``where``."""
    out: dict = {}
    for prom, sign in ((after, 1.0), (before, -1.0)):
        for labels, v in prom.get(name, []):
            if all(labels.get(k) == want for k, want in where.items()):
                key = labels.get(label, "")
                out[key] = out.get(key, 0.0) + sign * v
    return {k: round(v, 6) for k, v in sorted(out.items())}


def window_account(before: dict, after: dict, wall_s: float) -> dict:
    """The account of the stretch between two scrapes of the engine's
    ``/metrics`` (``harness.parse_prom``'s tables), ``wall_s`` apart."""
    def delta(name, label, **where):
        return _by_label(before, after, name, label, **where)

    loop = delta("pst_engine_loop_seconds_total", "state")
    busy = delta("pst_engine_device_busy_seconds_total", "").get("", 0.0)
    hist = "pst_engine_device_step_seconds"
    counts, sums = delta(hist + "_count", "kind"), delta(hist + "_sum", "kind")
    return {
        "wall_s": round(wall_s, 4),
        "loop_s": loop,
        "loop_sum_over_wall": round(sum(loop.values()) / wall_s, 5) if wall_s else None,
        "loop_cycles": delta("pst_engine_loop_cycles_total", "state"),
        "device_busy_s": busy,
        "device_busy_over_wall": round(busy / wall_s, 5) if wall_s else None,
        "device_idle_s": delta("pst_engine_device_idle_seconds_total", "state"),
        "seen_late_s": delta(
            "pst_engine_device_service_seconds_total", "kind", seen="late"),
        "stall_s": delta("pst_engine_stall_seconds_total", "cause"),
        "service": {
            kind: {"programs": int(n),
                   "mean_ms": round(sums.get(kind, 0.0) / n * 1e3, 4) if n else None}
            for kind, n in ((k, counts.get(k, 0.0)) for k in ("decode", "prefill"))},
    }


def _keep_scrapes(kept: list):
    """`harness.scrape`, keeping each answer with the time it was asked:
    the run's last two are its window's ends (the warm-up scrapes before
    them, nothing after)."""
    from perf import harness

    plain = harness.scrape

    def scrape(base: str) -> dict:
        prom = plain(base)
        # stamped as the answer arrives: a busy engine lets a scrape wait
        # for its turn (0.02-0.3 s) and renders it in a few milliseconds
        kept.append((time.monotonic(), prom))
        return prom

    harness.scrape = scrape


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
    scrapes: list = []
    _keep_scrapes(scrapes)
    try:
        result = run.run_cell(cell, seed, 50.0, trace, out_dir=out_dir,
                              bench=bench, data_dirs=dirs)
    except BenchError as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if len(scrapes) >= 2:
            (t0, before), (t1, after) = scrapes[-2:]
            account = window_account(before, after, t1 - t0)
            print("window account: " + json.dumps(account), file=sys.stderr,
                  flush=True)
            where = out_dir or os.path.join(ROOT, "perf_out", cell)
            with open(os.path.join(where, "window_account.json"), "w") as f:
                json.dump(account, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
