#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Everything else goes on
earlier lines and into ``--out`` (default ``perf_out/<workload>/`` in the
checkout). See ``perf/README.md``.

This parent never imports jax: the engine child holds the chip during the
window and the check, the reference child after it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import signal
import sys
import threading
import time

_T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import check, client, config as configs, end_to_end  # noqa: E402
from perf import harness, manifest, tokenizer, warmup  # noqa: E402
from perf.harness import BenchError, log  # noqa: E402

READY_TIMEOUT_S = 1000.0
REFERENCE_TIMEOUT_S = 900.0
TRACE_MS = 3000


def _expect_device(dev: dict, cfg, chips: int, peaks: dict) -> None:
    """No result without the chip: fail, naming what was found."""
    want = {"platform": "tpu", "attention_impl": "pallas",
            "pallas_interpret": False, "device_count": chips}
    if cfg.flag("--quantization") == "int4":
        want["int4_impl"] = "pallas"
    for key, val in want.items():
        if dev.get(key) != val:
            raise BenchError(
                f"engine resolved {key}={dev.get(key)!r}, the benchmark needs "
                f"{val!r} (device path: {json.dumps(dev)})")
    if dev.get("device_kind") not in peaks:
        raise BenchError(
            f"device_kind {dev.get('device_kind')!r} is not in perf/peaks.json "
            f"({sorted(peaks)}): no peaks, no result")


class Engine:
    """The engine child and what the harness asks of it."""

    def __init__(self, cfg, out_dir: str, profiling: bool, extra_env=None):
        self.cfg = cfg
        self.out_dir = out_dir
        self.memory_file = os.path.join(out_dir, "engine_memory.json")
        self.profile_dir = os.path.join(out_dir, "profile")
        tok_dir = tokenizer.write_tokenizer_dir(
            os.path.join(out_dir, "tokenizer"), cfg.hf["vocab_size"])
        port = harness.free_port()
        self.base = f"http://127.0.0.1:{port}"
        flags = ["--host", "127.0.0.1", "--port", str(port),
                 "--tokenizer", tok_dir, "--debug-requests-buffer", "8192"]
        if profiling:
            flags += ["--profiling", "--profile-dir", self.profile_dir]
        if os.path.exists(self.memory_file):
            os.remove(self.memory_file)
        self.child = harness.Child(
            "engine",
            [sys.executable, os.path.join(HERE, "launch_engine.py"), cfg.path,
             self.memory_file, *flags],
            harness.child_env(extra_env), out_dir)

    def wait_ready(self) -> dict:
        harness.wait_ready(f"{self.base}/ready", self.child, "engine /ready",
                           READY_TIMEOUT_S)
        return harness.get_json(f"{self.base}/version").get("device") or {}

    def complete(self, body: dict, timeout: float = 600.0) -> dict:
        return harness.post_json(f"{self.base}/v1/completions", body, timeout)

    def prove_tokenizer(self) -> None:
        vocab = self.cfg.hf["vocab_size"]
        out = self.complete({
            "model": self.cfg.name, "prompt": [5, 6, 7, 8], "max_tokens": 1,
            "temperature": 0.0, "ignore_eos": True, "logprobs": check.TOP_N})
        tokenizer.prove_in_use(out, vocab, check.TOP_N)

    def memory(self) -> list:
        """Ask the child (SIGUSR1) for its devices' memory readings."""
        self.child.signal(signal.SIGUSR1)
        t_end = time.monotonic() + 20
        while time.monotonic() < t_end:
            if os.path.exists(self.memory_file):
                with open(self.memory_file) as f:
                    return json.load(f)
            time.sleep(0.1)
        raise BenchError("the engine child did not report its memory")

    def stop(self) -> None:
        self.child.stop()


def prefill_contexts(engine: Engine, prompts: list) -> None:
    """Build the cache the traffic needs: each prompt once, one token out.
    One at a time: two together would share prefill steps as their timing
    falls, and meet other step shapes from run to run."""
    for p in prompts:
        try:
            engine.complete({"model": engine.cfg.name, "prompt": p,
                             "max_tokens": 1, "temperature": 0.0,
                             "ignore_eos": True})
        except Exception as e:  # noqa: BLE001
            raise BenchError(f"set-up prefill failed: {e}") from e


def reference_of(cfg, parsed: list, variants: list, out_dir: str,
                 timeout: float, extra_env: dict = None,
                 reference_dirs: list = None) -> dict:
    """Child 2 (``reference/run.py``) over the complete responses: the
    plain reference of the configuration's own module, teacher-forced on
    the system's tokens, for each of ``variants``. The request and the
    result stay in ``out_dir`` as ``reference_request.json`` / ``_result.json``."""
    request = {"config_file": cfg.path, "variants": variants,
               "reference_dirs": list(reference_dirs or []),
               "sequences": [{"id": p["id"], "tokens": p["tokens"],
                              "n_prompt": p["n_prompt"], "want": p["want"]}
                             for p in parsed if p["complete"]]}
    req_path = os.path.join(out_dir, "reference_request.json")
    res_path = os.path.join(out_dir, "reference_result.json")
    with open(req_path, "w") as f:
        json.dump(request, f)
    if not request["sequences"]:
        return {"variants": {v: [] for v in variants}, "seconds": 0.0,
                "platform": None}
    harness.run_python_child(
        "reference", [os.path.join(HERE, "reference", "run.py"), req_path,
                      res_path],
        harness.child_env(extra_env), out_dir, timeout)
    with open(res_path) as f:
        return json.load(f)


def _keep(out_dir: str, name: str, obj) -> None:
    """Intermediate files for a look by hand; nothing reads them back."""
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(obj, f)


def _profile_midway(engine: Engine, delay: float, out: dict) -> threading.Thread:
    def go():
        time.sleep(delay)
        t0 = time.monotonic()
        try:
            out["response"] = harness.post_json(
                f"{engine.base}/debug/profile",
                {"duration_ms": TRACE_MS, "dir": engine.profile_dir},
                timeout=120)
        except Exception as e:  # noqa: BLE001
            out["error"] = str(e)
        out["seconds"] = time.monotonic() - t0

    t = threading.Thread(target=go)
    t.start()
    return t


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             out_dir: str = None, require_chip: bool = True,
             bench: dict = None, extra_env: dict = None,
             data_dirs: dict = None, t_start: float = None) -> dict:
    """The whole sequence of one run; returns the result object.
    ``require_chip=False`` exists for the CPU rehearsal in ``tests/perf``
    only; the command line always requires the chip."""
    bench = bench or manifest.load()
    data_dirs = data_dirs or {}
    t_start = _T_START if t_start is None else t_start
    cell = manifest.cell(bench, workload)
    cfg = configs.load(cell["config_file"])
    mix = manifest.load_mix(cell["traffic"], data_dirs.get("traffic"))
    peaks = manifest.load_peaks()
    out_dir = out_dir or os.path.join(ROOT, "perf_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    gen = importlib.import_module(f"perf.generators.{mix['generator']}")
    vocab = cfg.hf["vocab_size"]
    timings = {}
    engine = Engine(cfg, out_dir, profiling=trace, extra_env=extra_env)
    try:
        dev = engine.wait_ready()
        timings["ready_s"] = time.monotonic() - t_start
        log(f"engine ready; device path {json.dumps(dev)}")
        if require_chip:
            _expect_device(dev, cfg, cell["chips"], peaks)
        engine.prove_tokenizer()
        plan = gen.plan(mix, seed, seconds, vocab)
        sessions = [client.Session(t) for t in plan["sessions"]]
        t0 = time.monotonic()
        prefill_contexts(engine, plan["setup_prompts"] + plan["sessions"])
        timings["history_prefill_s"] = time.monotonic() - t0
        timings["warmup_s"] = warmup.run(engine, gen, mix, plan, vocab)["seconds"]

        prom_before = harness.scrape(engine.base)
        profile = {}
        prof_thread = (_profile_midway(engine, max(seconds / 2 - TRACE_MS / 2e3, 0),
                                       profile) if trace else None)
        setup_s = time.monotonic() - t_start
        wall0 = time.time()
        records, closed_at = client.run_plan(
            engine.base, cfg.name, plan, sessions, seconds)
        window_wall = (wall0, time.time())
        prom_after = harness.scrape(engine.base)
        if prof_thread is not None:
            prof_thread.join()
        summary = client.summarize(records, seconds)
        log(f"window closed at {closed_at:.2f}s: attempted "
            f"{summary['attempted']}, failed {summary['failed']}, in flight at "
            f"close {summary['in_flight_at_close']}, errors {summary['errors']}")
        end_to_end.log_side_numbers(summary, seconds)
        log(f"step shapes first met inside the window: "
            f"{harness.counter_delta(prom_before, prom_after, 'pst_engine_compile_total'):.0f}"
            "; steps in the window by bucket (count, mean host-timed ms): "
            + json.dumps(harness.steps_by_bucket(prom_before, prom_after)))
        try:
            spans = harness.get_json(f"{engine.base}/debug/requests?limit=100000")
        except Exception as e:  # noqa: BLE001
            spans = {"error": str(e)}
        _keep(out_dir, "spans.json", spans)
        _keep(out_dir, "window.json", {
            "summary": {k: v for k, v in summary.items() if not isinstance(v, list)},
            "prom_after": {k: v for k, v in prom_after.items()
                           if k.startswith(("pst_engine", "vllm:", "pst:"))
                           and not k.endswith(("_bucket", "_created"))}})

        t0 = time.monotonic()
        seqs = check.check_set(mix, plan, sessions, seed, vocab)
        parsed = [check.parse_response(
            s, engine.complete(check.request_body(cfg.name, s["prompt"])))
            for s in seqs]
        timings["check_s"] = time.monotonic() - t0
        memory = engine.memory()
    finally:
        engine.stop()

    t0 = time.monotonic()
    reference = reference_of(cfg, parsed, ["none"], out_dir,
                             REFERENCE_TIMEOUT_S, extra_env,
                             data_dirs.get("reference"))
    timings["reference_s"] = time.monotonic() - t0
    verdict = check.compare(parsed, reference["variants"]["none"], cfg.check)
    compared = "check: " + json.dumps(
        {k: v for k, v in verdict.items() if k != "detail"})
    log(compared)
    # each number compared beside its limit, on standard error too: what a
    # record keeps of a run that is not correct
    print(compared, file=sys.stderr, flush=True)
    log("set-up split: " + json.dumps(timings))

    peak = max((m.get("peak_bytes_in_use") or m.get("bytes_in_use") or 0)
               for m in memory)
    device = {"platform": dev.get("platform"), "kind": dev.get("device_kind"),
              "count": dev.get("device_count"), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"], "attempted": summary["attempted"],
              "failed": summary["failed"], "device": device}
    ctx = {
        "summary": summary, "seconds": seconds, "setup_s": setup_s,
        "timings": timings, "prom_before": prom_before,
        "prom_after": prom_after, "spans": spans, "cfg": cfg, "cell": cell,
        "mix": mix, "peaks": peaks.get(dev.get("device_kind")),
        "out_dir": out_dir, "records": records, "window_wall": window_wall,
        "cost_dirs": data_dirs.get("cost"),
    }
    if not trace:
        result["metrics"] = end_to_end.metrics(bench, cell, ctx)
        return result
    from perf import host_trace, layers

    if "error" in profile or (profile.get("response") or {}).get("status") != "ok":
        if require_chip:
            raise BenchError(f"the profile was not captured: {profile}")
        ctx["trace"] = None
    else:
        ctx["trace"] = layers.reduce_trace(engine.profile_dir, out_dir, extra_env)
        ctx["step_ops"] = layers.step_ops(
            bench, cell, data_dirs.get("layer_metrics"))
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = layers.breakdown(
            ctx["trace"], host_trace.of_run(ctx))
    result["metrics"] = layers.metrics(
        bench, cell, ctx, data_dirs.get("layer_metrics"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for logs and intermediate files "
                         "(default perf_out/<workload>/ in the checkout)")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("perf/run.py: JAX_PLATFORMS=cpu: the benchmark measures on the "
              "chip only (tests/perf has the CPU rehearsal)", file=sys.stderr)
        return 3
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), out_dir=args.out)
    except BenchError as e:
        print(f"perf/run.py: no result: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
