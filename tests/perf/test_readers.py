"""The per-layer readers on hand-made inputs: each gives the number a
person computes from the same text, and None when there is nothing to read."""

import pytest

from perf.harness import parse_prom
from perf.readers import (harness_timing, prom_delta, prom_hist, span_quantile,
                          startup_phase, trace_idle)

BEFORE = parse_prom('''
# HELP x y
hits_total{model_name="m"} 100.0
queries_total{model_name="m"} 400.0
compiles_total{kind="decode",shape_bucket="b8"} 1.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="0.005"} 0.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="0.01"} 10.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="+Inf"} 10.0
step_seconds_count{batch_bucket="b8",kind="decode"} 10.0
step_seconds_sum{batch_bucket="b8",kind="decode"} 0.08
''')
AFTER = parse_prom('''
hits_total{model_name="m"} 1000.0
queries_total{model_name="m"} 1400.0
compiles_total{kind="decode",shape_bucket="b8"} 1.0
compiles_total{kind="prefill",shape_bucket="b1xt64"} 2.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="0.005"} 0.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="0.01"} 60.0
step_seconds_bucket{batch_bucket="b8",kind="decode",le="+Inf"} 110.0
step_seconds_bucket{batch_bucket="b1xt64",kind="prefill",le="0.005"} 0.0
step_seconds_bucket{batch_bucket="b1xt64",kind="prefill",le="0.01"} 0.0
step_seconds_bucket{batch_bucket="b1xt64",kind="prefill",le="+Inf"} 4.0
step_seconds_count{batch_bucket="b8",kind="decode"} 110.0
step_seconds_sum{batch_bucket="b8",kind="decode"} 1.58
step_seconds_count{batch_bucket="b1xt64",kind="prefill"} 4.0
step_seconds_sum{batch_bucket="b1xt64",kind="prefill"} 0.4
pst_engine_startup_seconds{phase="load"} 1.25
''')
CTX = {"prom_before": BEFORE, "prom_after": AFTER}


def test_prom_delta_counter_and_ratio():
    assert prom_delta.read({"counter": "compiles_total"}, CTX) == 2.0
    assert prom_delta.read({"counter": "compiles_total",
                            "labels": {"kind": "decode"}}, CTX) == 0.0
    share = prom_delta.read({"numerator": "hits_total",
                             "denominator": "queries_total", "scale": 100}, CTX)
    assert share == pytest.approx(90.0)  # (1000-100)/(1400-400)
    assert prom_delta.read({"counter": "absent_total"}, CTX) is None
    assert prom_delta.read({"numerator": "hits_total",
                            "denominator": "compiles_total",
                            "labels": {"kind": "decode"}}, CTX) is None


def test_prom_hist_mean_is_exact_and_quantile_interpolates():
    p = {"histogram": "step_seconds", "labels": {"kind": "decode"}, "scale": 1000}
    assert prom_hist.read(dict(p, stat="mean"), CTX) == pytest.approx(15.0)
    # 100 new samples: 50 in (5, 10] ms, 50 beyond 10 ms; the 25th percentile
    # lies half way through the (5, 10] bucket
    assert prom_hist.read(dict(p, stat=0.25), CTX) == pytest.approx(7.5)
    assert prom_hist.read({"histogram": "step_seconds", "stat": "mean",
                           "labels": {"kind": "prefill"}, "scale": 1000},
                          CTX) == pytest.approx(100.0)
    assert prom_hist.read({"histogram": "step_seconds", "stat": "mean",
                           "labels": {"kind": "encode"}}, CTX) is None


def test_span_quantile_takes_only_the_windows_requests():
    def req(start, queue_ms):
        return {"start_time": start, "spans": [
            {"name": "engine_request", "duration_ms": 99.0},
            {"name": "engine_queue", "duration_ms": queue_ms}]}

    ctx = {"window_wall": (100.0, 140.0),
           "spans": {"requests": [req(90.0, 500.0), req(101.0, 1.0),
                                  req(120.0, 3.0), req(150.0, 900.0)]}}
    assert span_quantile.read({"span": "engine_queue", "q": 50}, ctx) == 2.0
    assert span_quantile.read({"span": "nothing", "q": 50}, ctx) is None
    assert span_quantile.read({"span": "engine_queue", "q": 50},
                              {"window_wall": (0, 1), "spans": {"error": "x"}}) is None


def test_startup_and_harness_timings():
    assert startup_phase.read({"phase": "load"}, CTX) == 1.25
    assert startup_phase.read({"phase": "precompile"}, CTX) is None
    ctx = {"timings": {"warmup_s": 12.5}}
    assert harness_timing.read({"key": "warmup_s"}, ctx) == 12.5
    assert harness_timing.read({"key": "history_prefill_s"}, ctx) is None


def test_trace_idle_share():
    assert trace_idle.read({}, {"trace": {"busy_s": 1.5, "window_s": 2.0}}) == 25.0
    assert trace_idle.read({}, {"trace": None}) is None
