"""The cost function of decode attention over ``layer_types`` (full layers
read the context, window layers the window: ``perf/cost/
paged_attn_layer_types.py``) and the metric files the window / full
attention mix brought, on hand-computed steps and counters. Nothing here is
a device number."""

import os

import pytest

from perf import config as configs
from perf import cost as costs
from perf import manifest
from perf.readers import prom_delta, trace_roofline_counted, trace_step_roofline

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OPS = "^%paged_attn_decode"
NEW = ("kernel.paged_attn_decode_layer_types_roofline",
       "kernel.moe_experts_top8_roofline", "moe.top8_experts_touched_share",
       "moe.top8_held_pair_share", "moe.top8_busiest_expert_over_mean",
       "kv.mixed_window_resident_share", "kv.window_prefix_lost_share")


@pytest.fixture(scope="module")
def cfg():
    return configs.load("perf/configs/mellum2-ep4-cut.json")


def _step(rows, context, window=1024, seconds=None):
    step = {"rows": rows, "new_tokens": rows, "kv_tokens": rows * context,
            "window_tokens": rows * min(context, window)}
    if seconds is not None:
        step["ops_s"] = {OPS: seconds}
    return step


def test_layer_types_cost_by_hand(cfg):
    """24 rows at 8,192 tokens: the 7 full layers read the context, the 21
    window layers 1,024 tokens a row, 2 x 4 x 128 x 2 = 2,048 B a token and
    layer; queries in and results out in all 28."""
    c = costs.load("paged_attn_layer_types").cost(_step(24, 8192), cfg.hf, cfg)
    context = 24 * 8192 * 7 + 24 * 1024 * 21
    assert context == 1_892_352
    assert c["bytes"] == context * 2048 + 28 * 24 * 32 * 128 * 2 * 2
    assert c["flops"] == 4.0 * 32 * 128 * context
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # were every layer's pages kept for the whole context: 2.9 times the bytes
    whole = 24 * 8192 * 28 * 2048
    assert 2.8 < whole / c["bytes"] < 3.0


def test_layer_types_cost_below_the_window_charges_what_is_there(cfg):
    c = costs.load("paged_attn_layer_types").cost(
        _step(2, 300), cfg.hf, cfg)
    assert c["bytes"] == 2 * 300 * 28 * 2048 + 28 * 2 * 32 * 128 * 2 * 2


def test_layer_types_cost_takes_a_chained_step_with_a_finished_row(cfg):
    """A chained step reports the rows still alive as its new tokens (23 of
    24 members here): one token a row all the same."""
    cost = costs.load("paged_attn_layer_types")
    step = dict(_step(24, 4096), new_tokens=23)
    assert cost.cost(step, cfg.hf, cfg) == cost.cost(_step(24, 4096), cfg.hf, cfg)


@pytest.mark.parametrize("step,hf_over", [
    ({"rows": 0, "kv_tokens": 5, "window_tokens": 5}, {}),
    ({"rows": 4, "kv_tokens": 0, "window_tokens": 0}, {}),
    # a program without the window group writes no window_tokens
    ({"rows": 4, "new_tokens": 4, "kv_tokens": 4096}, {}),
    # a burst of several tokens a row is not what this counts
    ({"rows": 4, "new_tokens": 16, "kv_tokens": 4096, "window_tokens": 4096}, {}),
    ({"rows": 4, "new_tokens": 8, "kv_tokens": 4096, "window_tokens": 4096}, {}),
    # a configuration without layer_types (another class)
    (_step(4, 2048), {"layer_types": None}),
])
def test_layer_types_cost_refuses_what_it_cannot_count(cfg, step, hf_over):
    hf = {**cfg.hf, **hf_over}
    assert costs.load("paged_attn_layer_types").cost(step, hf, cfg) is None


def test_layer_types_roofline_is_least_over_measured(cfg):
    spec = manifest.load_layer_metric(NEW[0])
    assert spec["reader"] == "trace_step_roofline"
    assert spec["params"] == {"ops": OPS, "cost": "paged_attn_layer_types"}
    steps = [_step(24, 8192, seconds=0.0080), _step(24, 4096, seconds=0.0050)]
    ctx = {"host_trace": {"decode_steps": steps}, "peaks": PEAKS, "cfg": cfg}
    cost = costs.load("paged_attn_layer_types")
    least = sum(cost.cost(s, cfg.hf, cfg)["bytes"] / 819e9 for s in steps)
    share = trace_step_roofline.read(spec["params"], ctx)
    assert share == pytest.approx(least / 0.0130 * 100)
    assert 55 < share < 65
    # the parent's program: no window_tokens in a step, the metric is left out
    for s in steps:
        del s["window_tokens"]
    assert trace_step_roofline.read(spec["params"], ctx) is None
    # a reduction that did not time the kernel's operations
    ctx["host_trace"] = {"decode_steps": [_step(24, 8192)]}
    assert trace_step_roofline.read(spec["params"], ctx) is None


def test_the_expert_products_are_costed_by_the_accepted_module():
    """The banks as this class hands them to ``%gmm``: the 28 layers' 16
    experts seen as one bank of 448, gate and up one product of 1,792."""
    spec = manifest.load_layer_metric(NEW[1])
    assert spec["reader"] == "trace_roofline_counted"
    assert spec["params"]["cost"] == "moe_experts_latent"
    up = ("%gmm.5 = f32[256,1792]{1,0} custom-call(s32[448]{0} %a, "
          "bf16[256,2304]{1,0} %x, bf16[448,2304,1792]{2,1,0} %w)")
    down = ("%gmm.6 = f32[256,2304]{1,0} custom-call(s32[448]{0} %a, "
            "bf16[256,896]{1,0} %x, bf16[448,896,2304]{2,1,0} %w)")
    counted = {"experts_touched": 15.0, "pairs_held": 48.0}
    cost = costs.load("moe_experts_latent")
    c = cost.cost({"text": up, "count": 28, "counted": counted}, {}, None)
    assert c["bytes"] == 28 * (15 * 2304 * 1792 * 2 + 48 * (2304 * 2 + 1792 * 4))
    assert c["flops"] == 28 * 2 * 48 * 2304 * 1792
    d = cost.cost({"text": down, "count": 28, "counted": counted}, {}, None)
    # a step's 28 layers read 5.2 GB of touched experts: 6.3 ms at 819 GB/s
    assert 5.1e9 < c["bytes"] + d["bytes"] < 5.3e9
    trace = {"calls": [
        {"text": up, "count": 28, "seconds": 28 * 200e-6},
        {"text": down, "count": 28, "seconds": 28 * 100e-6}]}
    ctx = {"trace": trace, "peaks": PEAKS, "cfg": type("C", (), {"hf": {}})(),
           "prom_before": {}, "prom_after": {}}
    assert trace_roofline_counted.read(spec["params"], ctx) is None  # no counters
    names = ("pst:moe_experts_touched_total", "pst:moe_pairs_held_total",
             "pst:moe_layer_steps_total")
    ctx["prom_before"] = {n: [({}, 0.0)] for n in names}
    ctx["prom_after"] = dict(zip(names, ([({}, 15.0 * 280)], [({}, 48.0 * 280)],
                                         [({}, 280.0)])))
    share = trace_roofline_counted.read(spec["params"], ctx)
    assert share == pytest.approx(
        (c["bytes"] + d["bytes"]) / 819e9 / (28 * 300e-6) * 100)
    assert 70 < share < 80


@pytest.mark.parametrize("name,before,after,want", [
    ("moe.top8_experts_touched_share",
     {"pst:moe_experts_touched_total": 10, "pst:moe_layer_steps_total": 1},
     {"pst:moe_experts_touched_total": 10 + 12 * 56,
      "pst:moe_layer_steps_total": 57}, 75.0),
    ("moe.top8_held_pair_share",
     {"pst:moe_pairs_held_total": 100, "pst:moe_pairs_routed_total": 200},
     {"pst:moe_pairs_held_total": 1100, "pst:moe_pairs_routed_total": 4200}, 25.0),
    ("moe.top8_busiest_expert_over_mean",
     {"pst:moe_busiest_expert_pairs_total": 0, "pst:moe_pairs_held_total": 0},
     {"pst:moe_busiest_expert_pairs_total": 600, "pst:moe_pairs_held_total": 4800},
     2.0),
    ("kv.mixed_window_resident_share",
     {"pst:window_page_steps_total": 50,
      "pst:window_whole_context_page_steps_total": 100},
     {"pst:window_page_steps_total": 950,
      "pst:window_whole_context_page_steps_total": 6100}, 15.0),
    ("kv.window_prefix_lost_share",
     {"pst:window_prefix_tokens_lost_total": 0,
      "vllm:gpu_prefix_cache_queries_total": 1000},
     {"pst:window_prefix_tokens_lost_total": 1024,
      "vllm:gpu_prefix_cache_queries_total": 205800}, 0.5),
])
def test_counter_metrics_read_their_deltas(name, before, after, want):
    spec = manifest.load_layer_metric(name)
    assert spec["reader"] == "prom_delta"
    ctx = {"prom_before": {k: [({}, float(v))] for k, v in before.items()},
           "prom_after": {k: [({}, float(v))] for k, v in after.items()}}
    assert prom_delta.read(spec["params"], ctx) == pytest.approx(want)
    # a program without the counters (the parent's): left out, not raised
    assert prom_delta.read(
        spec["params"], {"prom_before": {}, "prom_after": {}}) is None


def test_the_published_cell_is_the_accepted_benchmark_extended():
    """The new cell's files are found by name from the repository's own
    ``BENCHMARK.json``; its seven metrics list it and nothing else does."""
    bench = manifest.load(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    cell = "mellum2-ep4-cut.codechat-closed"
    listed = [m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [])]
    assert sorted(listed) == sorted(NEW)
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for name in listed:
        spec = manifest.load_layer_metric(name)
        if "cost" in spec["params"]:
            assert hasattr(costs.load(spec["params"]["cost"]), "cost")
    cfg = configs.load("perf/configs/mellum2-ep4-cut.json")
    assert cfg.reference == "mellum" and cfg.hf["model_type"] == "mellum"
    assert cfg.raw["reduced"] == ["num_experts", "vocab_size"]
    assert cfg.raw["published"] == {"num_experts": 64, "vocab_size": 98304}
    assert cfg.hf["num_hidden_layers"] == 28  # no depth cut
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
