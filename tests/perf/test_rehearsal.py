"""The whole sequence of ``perf/run.py`` on the CPU at a tiny size, through
the real server: child engine, tokenizer proof, set-up, warm-up, window,
check, reference child, per-layer readers, and the contract's last line.

The configurations, traffic mixes and the extra per-layer metric are files
under ``tests/perf/data`` that the harness finds by name: adding them took
no edit to a file under ``perf/``. Nothing here is a device number: the
platform is ``cpu`` and says so, and the command line itself refuses it.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from perf import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")]}
# The suite's conftest asks for interpreted Pallas int4 and eight virtual
# devices; the rehearsal's children need neither.
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.tiny.json"))


def _run(bench, workload, trace, tmp_path, seed=2**31 + 99):
    result = run.run_cell(
        workload, seed, 4.0, trace, out_dir=str(tmp_path), require_chip=False,
        bench=bench, extra_env=ENV, data_dirs=DIRS, t_start=time.monotonic())
    line = json.loads(json.dumps(result))  # what would be printed
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"  # never passed off as a chip
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    return line


def test_open_loop_sessions_cell_end_to_end(bench, tmp_path):
    cell = "tiny-dense-int4.tiny-sessions"
    line = _run(bench, cell, False, tmp_path)
    want = {m["name"] for m in manifest.metrics_of(bench, "end_to_end", cell)}
    assert set(line["metrics"]) == want == {
        "ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms", "itl_p50_ms", "out_tok_per_s",
        "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_closed_loop_sessions_cell_traced_reports_per_layer_metrics(bench, tmp_path):
    line = _run(bench, "tiny-dense-int4.tiny-sessions-closed", True, tmp_path)
    got = set(line["metrics"])
    assert {"client.ttft_p50_ms", "client.itl_p95_ms", "sched.cached_prompt_share",
            "runner.decode_step_mean_ms", "runner.prefill_step_mean_ms",
            "engine.queue_wait_p95_ms", "runner.compiles_in_window",
            "startup.ready_s", "test.requests_served"} <= got
    # no chip, no profile: the trace readers find nothing and are left out
    assert not got & {"device.idle_share", "kernel.int4_matmul_roofline"}
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert 50 < line["metrics"]["sched.cached_prompt_share"]["value"] <= 100
    assert line["metrics"]["test.requests_served"]["value"] >= line["attempted"]


def test_moe_closed_loop_cell_end_to_end(bench, tmp_path):
    line = _run(bench, "tiny-moe.tiny-closed", False, tmp_path)
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}


def test_closed_loop_rate_counts_every_token_streamed_in_the_window():
    from perf import client, end_to_end

    done = client.Record(due=0.0, events=[1.0, 2.0], event_tokens=[1, 1],
                         want_tokens=2, done=2.0)
    straddles = client.Record(due=3.0, events=[3.5, 3.9], event_tokens=[1, 1],
                              want_tokens=9)
    summary = client.summarize([done, straddles], 4.0)
    assert summary["output_tokens_completed"] == 2
    assert summary["output_tokens_streamed"] == 4
    ctx = {"summary": summary, "seconds": 4.0, "cell": {"chips": 1}}
    assert end_to_end.COMPUTE["out_tok_per_s"](ctx) == 1.0


def test_the_command_refuses_to_measure_without_the_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         manifest.load()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")


def test_device_path_check_names_what_it_found():
    from perf import config as configs
    from perf.harness import BenchError

    cfg = configs.load(os.path.join(DATA, "configs", "tiny-dense-int4.json"))
    peaks = manifest.load_peaks()
    good = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
            "attention_impl": "pallas", "int4_impl": "pallas",
            "pallas_interpret": False}
    run._expect_device(good, cfg, 1, peaks)
    for key, bad in (("platform", "cpu"), ("device_kind", "TPU v9"),
                     ("device_count", 4), ("attention_impl", "gather"),
                     ("int4_impl", "xla"), ("pallas_interpret", True)):
        with pytest.raises(BenchError, match=key if key != "device_kind" else "peaks"):
            run._expect_device(dict(good, **{key: bad}), cfg, 1, peaks)
