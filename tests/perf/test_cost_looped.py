"""The two cost functions of a looped dense stack (decode attention over
``layers x passes`` cache slots: ``perf/cost/paged_attn_looped.py``; a whole
decode step: ``perf/cost/looped_decode_step.py``), the reader of a whole
step's share (``perf/readers/trace_step_module_roofline.py``) and the three
metric files, on hand-computed steps and counters. Nothing here is a device
number.

The three metrics are **not yet entries of ``BENCHMARK.json``**: an entry
goes at the end of ``per_layer``, and ``test_cost_layer_types.py:179`` pins
that list's last seven names (PERF.md §7, "Open since PR 49"). Their files
wait in ``data/layer_metrics/`` and their entries in
``data/BENCHMARK.looped.json``, in the form a ``benchmark`` PR appends."""

import json
import os

import types

import pytest

from perf import config as configs
from perf import cost as costs
from perf import manifest
from perf.readers import prom_delta, trace_step_module_roofline, trace_step_roofline

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OPS = "^%paged_attn_decode"
DATA = os.path.join(os.path.dirname(__file__), "data")
METRIC_DIRS = [os.path.join(DATA, "layer_metrics")]
CELL = "ouro-2.6b.fewshot-closed"
NEW = ("kernel.paged_attn_decode_looped_roofline",
       "runner.looped_decode_step_mfu", "model.layer_passes_per_decode_step")
A_LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632  # one layer's matrices
HEAD = 49152 * 2048


@pytest.fixture(scope="module")
def cfg():
    return configs.load("perf/configs/ouro-2.6b.json")


def _step(rows, context, **more):
    return {"rows": rows, "new_tokens": rows, "kv_tokens": rows * context,
            "passes": 4, **more}


def test_looped_attention_cost_by_hand(cfg):
    """16 rows at 720 tokens: every row's keys and values in each of 4 x 48
    = 192 slots, 2 x 16 x 128 x 2 = 8,192 B a token and slot (1.5 MiB a
    token over all of them); queries in and results out in all 192."""
    c = costs.load("paged_attn_looped").cost(_step(16, 720), cfg.hf, cfg)
    assert 192 * 8192 == 1_572_864
    assert c["bytes"] == 16 * 720 * 1_572_864 + 192 * 16 * 16 * 128 * 2 * 2
    assert c["flops"] == 4.0 * 16 * 128 * 16 * 720 * 192
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # four times what the same widths cost with one layer of pages a layer
    plain = costs.load("paged_attn").cost(_step(16, 720), cfg.hf, cfg)
    assert c["bytes"] == 4 * plain["bytes"] and c["flops"] == 4 * plain["flops"]


def test_looped_attention_cost_needs_the_passes(cfg):
    cost = costs.load("paged_attn_looped").cost
    assert cost(_step(16, 720), {k: v for k, v in cfg.hf.items()
                                 if k != "total_ut_steps"}, cfg) is None
    assert cost({"rows": 0, "kv_tokens": 0}, cfg.hf, cfg) is None


def test_whole_step_cost_by_hand(cfg):
    """16 rows at 720 tokens: the 48 layers' weights four times (4.93 GB a
    pass with the norms), the head once, the pages of 192 slots, the rows'
    activations; 2 x parameters x passes x rows operations, the head's and
    the attention's."""
    assert A_LAYER == 51_380_224
    c = costs.load("looped_decode_step").cost(_step(16, 720), cfg.hf, cfg)
    pages = costs.load("paged_attn_looped").cost(_step(16, 720), cfg.hf, cfg)
    weights = (A_LAYER * 2 + 4 * 2048 * 2) * 48 * 4 + HEAD * 2
    assert 19.93e9 < weights < 19.94e9  # the issue's 4 x 4.933 + 0.201 GB
    activations = 2 * (4 * 2048 + 4 * 2048 + 3 * 5632) * 192 * 16
    assert c["bytes"] == weights + activations + pages["bytes"]
    assert c["flops"] == (2.0 * A_LAYER * 192 + 2.0 * HEAD) * 16 + pages["flops"]
    # memory decides, by a factor of 27: 46.7 ms against 1.7
    assert 0.045 < c["bytes"] / 819e9 < 0.048
    assert 25 < (c["bytes"] / 819e9) / (c["flops"] / 197e12) < 30


def test_whole_step_cost_of_a_burst_reads_the_weights_once_a_token(cfg):
    one = costs.load("looped_decode_step").cost(_step(16, 720), cfg.hf, cfg)
    burst = costs.load("looped_decode_step").cost(
        dict(_step(16, 721), new_tokens=32), cfg.hf, cfg)
    assert 1.99 < burst["bytes"] / one["bytes"] < 2.01
    assert 1.99 < burst["flops"] / one["flops"] < 2.01


def test_whole_step_cost_follows_the_stored_width():
    cfg = configs.load("perf/configs/ouro-2.6b.json")
    int4 = types.SimpleNamespace(
        hf=cfg.hf, flag=lambda name: "int4" if name == "--quantization" else None)
    a = costs.load("looped_decode_step").cost(_step(16, 720), cfg.hf, cfg)
    b = costs.load("looped_decode_step").cost(_step(16, 720), cfg.hf, int4)
    assert b["flops"] == a["flops"]
    saved = A_LAYER * 192 * (2.0 - (0.5 + 4.0 / 128))
    assert a["bytes"] - b["bytes"] == pytest.approx(saved)


def _ctx(cfg, steps, trace=True):
    return {"trace": trace, "peaks": PEAKS, "cfg": cfg,
            "host_trace": {"decode_steps": steps} if trace else None}


def test_the_whole_steps_share_is_least_time_over_the_programs_time(cfg):
    cost = costs.load("looped_decode_step").cost
    steps = [dict(_step(16, 700), module_s=0.060, ops_s={}),
             dict(_step(16, 740), module_s=0.058, ops_s={})]
    least = sum(cost(s, cfg.hf, cfg)["bytes"] / 819e9 for s in steps)
    got = trace_step_module_roofline.read({"cost": "looped_decode_step"},
                                          _ctx(cfg, steps))
    assert got == pytest.approx(least / 0.118 * 100.0)
    assert 70 < got < 85
    # a program that ran no time is no step; no step at all is no number
    steps.append(dict(_step(16, 700), module_s=0.0))
    assert trace_step_module_roofline.read(
        {"cost": "looped_decode_step"}, _ctx(cfg, steps)) == pytest.approx(got)
    assert trace_step_module_roofline.read(
        {"cost": "looped_decode_step"}, _ctx(cfg, [])) is None


def test_the_whole_steps_share_is_absent_without_a_trace_or_the_passes(cfg):
    params = {"cost": "looped_decode_step"}
    assert trace_step_module_roofline.read(params, _ctx(cfg, [], False)) is None
    plain = types.SimpleNamespace(
        hf={k: v for k, v in cfg.hf.items() if k != "total_ut_steps"},
        flag=cfg.flag)
    steps = [dict(_step(16, 700), module_s=0.060)]
    assert trace_step_module_roofline.read(params, _ctx(plain, steps)) is None


def test_the_kernels_share_reads_the_kernels_time_inside_the_step(cfg):
    spec = manifest.load_layer_metric(NEW[0], METRIC_DIRS)
    assert spec["reader"] == "trace_step_roofline"
    assert spec["params"] == {"ops": OPS, "cost": "paged_attn_looped"}
    steps = [dict(_step(16, 720), module_s=0.06, ops_s={OPS: 0.030})]
    got = trace_step_roofline.read(spec["params"], _ctx(cfg, steps))
    pages = costs.load("paged_attn_looped").cost(steps[0], cfg.hf, cfg)
    assert got == pytest.approx(pages["bytes"] / 819e9 / 0.030 * 100.0)
    assert 70 < got < 80


def test_the_passes_a_dispatch_ran_are_read_in_units_of_48_layers():
    spec = manifest.load_layer_metric(NEW[2], METRIC_DIRS)
    before = {"pst:decode_layer_passes_total": [({}, 192.0 * 10)],
              "pst:decode_dispatches_total": [({}, 10.0)]}
    after = {"pst:decode_layer_passes_total": [({}, 192.0 * 510)],
             "pst:decode_dispatches_total": [({}, 510.0)]}
    ctx = {"prom_before": before, "prom_after": after}
    assert prom_delta.read(spec["params"], ctx) == pytest.approx(4.0)
    # a program without the counter (the parent) leaves the metric out
    del after["pst:decode_layer_passes_total"]
    assert prom_delta.read(spec["params"], ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_the_metric_waits_as_an_entry_a_benchmark_pr_appends(name):
    """Laid over the accepted benchmark the way ``test_manifest.py`` lays
    ``BENCHMARK.later.json``, the cell owes the metric and no other cell
    does; the accepted benchmark itself does not have it yet."""
    accepted = manifest.load()
    assert name not in {m["name"] for m in accepted["per_layer"]}
    assert CELL in {w["name"] for w in accepted["workloads"]}
    with open(os.path.join(DATA, "BENCHMARK.looped.json")) as f:
        more = json.load(f)
    bench = dict(accepted, per_layer=accepted["per_layer"] + more["per_layer"])
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "itl_p50_ms"
    assert entry in manifest.metrics_of(bench, "per_layer", CELL)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert entry not in manifest.metrics_of(bench, "per_layer", w["name"])
    spec = manifest.load_layer_metric(name, METRIC_DIRS)
    assert set(spec) == {"what", "reader", "params"} and CELL not in str(spec)
