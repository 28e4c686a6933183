"""The eight per-layer metrics of the engine's own clock for the device
(PR 51): the reader of a capture's ``pst.ready`` stamps
(``perf/readers/trace_ready_clock.py``) on a hand-made trace whose every
number a person can follow and on a slice recorded on the chip
(``data/trace_v5e_ready_slice.json``; its ``_note`` says how it was cut), the
metric files, and the six that need no capture through the whole sequence of
``perf/run.py`` on the CPU at a tiny size. Nothing here is a device number.

The eight are **not yet entries of ``BENCHMARK.json``**: an entry goes at the
end of ``per_layer``, and ``test_cost_layer_types.py:179`` pins that list's
last seven names (PERF.md §7). Their files wait in ``data/layer_metrics/``
and their entries in ``data/BENCHMARK.service-clock.json``, in the form a
``benchmark`` PR appends."""

import importlib.util
import json
import os
import time

import pytest

from perf import manifest, run
from perf.readers import prom_delta, prom_hist, trace_ready_clock

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRIC_DIRS = [os.path.join(DATA, "layer_metrics")]
NEW = ("runner.decode_service_mean_ms", "runner.prefill_service_mean_ms",
       "runner.ready_lag_p95_ms", "runner.service_clock_error_pct",
       "engine.loop_decode_s", "engine.loop_prefill_s", "engine.loop_no_work_s",
       "engine.device_idle_host_s")
MS = 1e6  # ns


def _ready(at_ms, kind, service_ms, seen="poll", bucket="b16xn1", queued_ms=0.0):
    return ["pst.ready", at_ms * MS, 1200.0, {
        "kind": kind, "bucket": bucket, "service_us": int(service_ms * 1e3),
        "queued_us": int(queued_ms * 1e3), "seen": seen}]


def hand_made() -> dict:
    """Three chained decode programs of 10 ms with a 30 ms prefill between
    the second and the third, each stamped 0.4-1.0 ms after its end (the
    prefill 5 ms late, by a first poll that found it ready); a fourth stamp
    whose program ended before the capture began; a splice program and an
    encode's stamp, which belong to no kind."""
    modules = [["jit_pst_decode_step_chained(7)", 0.0, 10 * MS],
               ["jit_pst_decode_step_chained(7)", 10 * MS, 10 * MS],
               ["jit_pst_prefill_step(9)", 20 * MS, 30 * MS],
               ["jit_pst_chain_splice(3)", 50 * MS, 0.01 * MS],
               ["jit_pst_decode_step_chained(7)", 50.01 * MS, 10 * MS]]
    thread = [
        _ready(-0.5, "decode", 10.0),  # its program is not in the capture
        ["pst.wait", 1 * MS, 9.5 * MS, {"kind": "decode"}],
        _ready(10.4, "decode", 10.9),
        _ready(20.6, "decode", 10.2),
        _ready(55.0, "prefill", 34.4, seen="late", bucket="b1xt1024"),
        _ready(61.01, "decode", 6.01, queued_ms=34.0),
        _ready(61.5, "encode", 0.3, bucket="t64"),
    ]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": thread}]},
        {"name": "/device:TPU:0", "interval": [0.0, 61 * MS], "lines": [
            {"name": "XLA Modules", "events": modules}]}]}


def test_stamps_join_their_programs_by_time_and_the_errors_telescope():
    got = trace_ready_clock.reduce(hand_made())
    assert (got["stamps"], got["joined"], got["late"]) == (6, 4, 1)
    # 0.4, 0.6, 5.0 and 1.0 ms after the programs' ends
    assert got["lag_ms"]["max"] == pytest.approx(5.0)
    assert got["lag_ms"]["p50"] == pytest.approx(0.8)
    assert got["lag_ms"]["p95"] == pytest.approx(1.0 + 0.85 * 4.0)
    # 10.9 + 10.2 + 34.4 + 6.01 against 10 + 10 + 30 + 10: the late stamp
    # lengthens the prefill and shortens the step behind it
    assert got["service_s"] == pytest.approx(0.06151)
    assert got["module_s"] == pytest.approx(0.060)
    assert got["error_pct"] == pytest.approx(1.51 / 60 * 100)
    assert got["by_kind"]["decode"] == [3, pytest.approx(0.02711), pytest.approx(0.030)]
    assert got["by_bucket"]["prefill b1xt1024"] == [
        1, pytest.approx(0.0344), pytest.approx(0.030)]


def test_a_stamp_a_few_microseconds_before_its_programs_end_still_finds_it():
    """A poll reads the clock, then asks: a program that ends between the
    two is stamped just before its end on the capture's clock."""
    t = hand_made()
    t["planes"][0]["lines"][0]["events"] = [_ready(9.99, "decode", 10.0)]
    got = trace_ready_clock.reduce(t)
    assert got["joined"] == 1 and got["lag_ms"]["max"] == pytest.approx(-0.01)


def _ctx(reduced):
    return {"trace": {"window_s": 3.0}, "ready_clock": reduced}


def test_the_readers_two_stats_and_their_absence():
    reduced = trace_ready_clock.reduce(hand_made())
    lag = manifest.load_layer_metric(NEW[2], METRIC_DIRS)
    err = manifest.load_layer_metric(NEW[3], METRIC_DIRS)
    assert lag["reader"] == err["reader"] == "trace_ready_clock"
    assert trace_ready_clock.read(lag["params"], _ctx(reduced)) == \
        pytest.approx(4.4)
    assert trace_ready_clock.read(err["params"], _ctx(reduced)) == \
        pytest.approx(2.5166667)
    # a program without the clock writes no pst.ready: nothing, not a raise
    bare = hand_made()
    bare["planes"][0]["lines"][0]["events"] = [
        ["pst.wait", 1 * MS, 9 * MS, {"kind": "decode"}]]
    none = trace_ready_clock.reduce(bare)
    assert none["joined"] == 0 and none["error_pct"] is None
    for spec in (lag, err):
        assert trace_ready_clock.read(spec["params"], _ctx(none)) is None
        assert trace_ready_clock.read(spec["params"], {"trace": None}) is None
    with pytest.raises(ValueError):
        trace_ready_clock.read({"stat": "mean"}, _ctx(reduced))


def test_a_slice_recorded_on_the_chip():
    """0.6 s of a dense cell's capture (my chip run, PR 51, seed 3520000017)
    cut to its pst.ready stamps and its step programs. Every stamp but the
    first, whose program ended before the cut, finds its program; the
    runtime reports an array ready 1.6 ms after its program's end in this
    cell and a poll comes every millisecond, so a stamp lags by 1.6-2.5 ms,
    the same for every program: the lags cancel between two stamps, and the
    clock's sum is the programs' to a hundredth of a per cent."""
    with open(os.path.join(DATA, "trace_v5e_ready_slice.json")) as f:
        recorded = json.load(f)
    got = trace_ready_clock.reduce(recorded)
    assert (got["stamps"], got["joined"], got["late"]) == (33, 32, 0)
    assert got["lag_ms"]["p50"] == pytest.approx(1.790047)
    assert got["lag_ms"]["p95"] == pytest.approx(2.295955, rel=1e-5)
    assert got["lag_ms"]["max"] < 2.5
    assert got["error_pct"] == pytest.approx(0.010725, rel=1e-3)
    n, service_s, module_s = got["by_kind"]["decode"]
    assert n == 27 and abs(service_s - module_s) / n < 2e-5  # 12 us a program
    assert got["by_bucket"]["prefill b1xt256"] == [
        2, pytest.approx(0.079367), pytest.approx(0.079521166)]


def test_the_six_counters_and_histograms_read_by_hand():
    before = {
        "pst_engine_device_step_seconds_count": [({"kind": "decode"}, 100.0)],
        "pst_engine_device_step_seconds_sum": [({"kind": "decode"}, 1.7)],
        "pst_engine_loop_seconds_total": [
            ({"state": "decode"}, 5.0), ({"state": "prefill"}, 1.0),
            ({"state": "no_work"}, 30.0)],
        "pst_engine_device_idle_seconds_total": [
            ({"state": "host"}, 0.1), ({"state": "no_work"}, 29.0)],
    }
    after = {
        "pst_engine_device_step_seconds_count": [
            ({"kind": "decode"}, 2900.0), ({"kind": "prefill"}, 40.0)],
        "pst_engine_device_step_seconds_sum": [
            ({"kind": "decode"}, 48.46), ({"kind": "prefill"}, 1.0)],
        "pst_engine_loop_seconds_total": [
            ({"state": "decode"}, 51.5), ({"state": "prefill"}, 4.25),
            ({"state": "no_work"}, 30.25)],
        "pst_engine_device_idle_seconds_total": [
            ({"state": "host"}, 0.35), ({"state": "no_work"}, 29.0)],
    }
    ctx = {"prom_before": before, "prom_after": after}
    want = {NEW[0]: 16.7, NEW[1]: 25.0, NEW[4]: 46.5, NEW[5]: 3.25,
            NEW[6]: 0.25, NEW[7]: 0.25}
    for name, value in want.items():
        spec = manifest.load_layer_metric(name, METRIC_DIRS)
        reader = {"prom_hist": prom_hist, "prom_delta": prom_delta}[spec["reader"]]
        assert reader.read(spec["params"], ctx) == pytest.approx(value), name
    # a program without the clock has none of the names: nothing to read
    for name in want:
        spec = manifest.load_layer_metric(name, METRIC_DIRS)
        reader = {"prom_hist": prom_hist, "prom_delta": prom_delta}[spec["reader"]]
        assert reader.read(spec["params"], {"prom_before": {}, "prom_after": {}}) is None


def test_the_entries_wait_in_the_form_a_benchmark_pr_appends():
    with open(os.path.join(DATA, "BENCHMARK.service-clock.json")) as f:
        more = json.load(f)
    bench = manifest.load()
    entries = more["per_layer"]
    assert tuple(e["name"] for e in entries) == NEW
    accepted = {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for e in entries:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves"}
        assert e["name"] not in accepted and e["layer"] in layers
        assert e["moves"] in e2e and e["better"] in ("lower", "higher")
        manifest.load_layer_metric(e["name"], METRIC_DIRS)  # its file is there
    # every cell reports them: none lists workloads
    laid = dict(bench, per_layer=bench["per_layer"] + entries)
    for cell in bench["workloads"]:
        got = {m["name"] for m in manifest.metrics_of(laid, "per_layer", cell["name"])}
        assert set(NEW) <= got


def _cell_extra():
    spec = importlib.util.spec_from_file_location(
        "tpu_cell_extra_under_test", os.path.join(ROOT, "scripts", "tpu_cell_extra.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_tiny_cells_traced_run_reports_the_six_and_its_window_sums_to_the_wall(
        tmp_path):
    """The whole sequence on the CPU with the entries laid over the tiny
    benchmark, as ``scripts/tpu_cell_extra.py`` lays them over the accepted
    one: the six that need no capture are in the line (no chip, no profile:
    the two trace readers are left out), and the window account of the
    run's two scrapes sums to the wall between them."""
    from perf import harness

    with open(os.path.join(DATA, "BENCHMARK.service-clock.json")) as f:
        more = json.load(f)
    bench = manifest.load(os.path.join(DATA, "BENCHMARK.tiny.json"))
    bench = dict(bench, per_layer=bench["per_layer"] + more["per_layer"])
    extra = _cell_extra()
    scrapes, plain = [], harness.scrape
    extra._keep_scrapes(scrapes)
    try:
        line = run.run_cell(
            "tiny-dense-int4.tiny-sessions-closed", 2**31 + 51, 4.0, True,
            out_dir=str(tmp_path), require_chip=False, bench=bench,
            extra_env={"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "",
                       "XLA_FLAGS": ""},
            data_dirs={"traffic": [os.path.join(DATA, "traffic")],
                       "layer_metrics": METRIC_DIRS},
            t_start=time.monotonic())
    finally:
        harness.scrape = plain
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    # (on the CPU a tiny prefill program may be over before its first poll:
    # seen late, and then not in the histogram)
    assert {NEW[2], NEW[3]} <= set(NEW) - set(got) <= {NEW[1], NEW[2], NEW[3]}
    assert got[NEW[0]]["value"] > 0 and got[NEW[4]]["value"] > 0
    (t0, before), (t1, after) = scrapes[-2:]
    account = extra.window_account(before, after, t1 - t0)
    # to the stretch in progress at either scrape: here a cycle that compiles
    # (a tenth of a second of 4; tests/test_ready_clock.py holds 1 %)
    assert account["loop_sum_over_wall"] == pytest.approx(1.0, abs=0.1)
    assert account["loop_cycles"]["decode"] > 0
    assert 0 < account["device_busy_over_wall"] <= 1.02
    assert set(account["stall_s"]) >= {"device", "machine", "unknown"}
    assert account["service"]["decode"]["programs"] > 0
    loop = sum(got[n]["value"] for n in NEW[4:7])
    assert loop <= sum(account["loop_s"].values()) + 1e-6
