"""The nine per-layer metrics of the self-drafting cell (PR 53), which wait in
``data/`` beside PR 49's and PR 51's (``data/BENCHMARK.mtp.json``; PERF.md
section 7): their files, their cost functions' arithmetic at the published
widths, and what a program without the spans and counters gives."""

import json
import os

import pytest

from perf import config as configs
from perf import cost as costs
from perf import manifest
from perf.cost import mtp_decode_step, paged_attn_verify
from perf.readers import prom_delta, trace_step_module_roofline, trace_step_roofline

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = [os.path.join(DATA, "layer_metrics")]
CELL = "k-exaone-ep8-cut.thinking-closed"
NEW = ("spec.mtp_accepted_share", "spec.mtp_tokens_per_row_step",
       "runner.mtp_decode_step_mfu", "kernel.paged_attn_verify_roofline",
       "kernel.moe_experts_shared_top8_roofline",
       "moe.mtp_top8_held_pair_share", "moe.mtp_top8_experts_touched_share",
       "moe.mtp_top8_busiest_expert_over_mean", "kv.mtp_window_resident_share")
# a verify-and-draft step of the cell: 64 rows at 3.7k tokens of context
STEP = {"kind": "decode", "step": "mtp_verify", "bucket": "b64xk1", "rows": 64,
        "new_tokens": 128, "kv_tokens": 64 * 3700, "window_tokens": 64 * 128,
        "kv_pages": 64 * 30, "draft_positions": 64, "accepted": 0}


@pytest.fixture(scope="module")
def cfg():
    return configs.load("perf/configs/k-exaone-ep8-cut.json")


def _spec(name):
    with open(os.path.join(DIRS[0], name + ".json")) as f:
        return json.load(f)


def test_the_waiting_entries_name_the_cell_and_their_files_exist():
    with open(os.path.join(DATA, "BENCHMARK.mtp.json")) as f:
        waiting = json.load(f)["per_layer"]
    assert tuple(m["name"] for m in waiting) == NEW
    bench = manifest.load(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    accepted = {m["name"] for m in bench["per_layer"]}
    for m in waiting:
        assert m["workloads"] == [CELL] and CELL in cells
        assert m["moves"] in e2e and m["layer"] in layers
        assert m["name"] not in accepted
        spec = _spec(m["name"])
        assert spec["reader"] and spec["what"]
        if "cost" in spec["params"]:
            assert hasattr(costs.load(spec["params"]["cost"]), "cost")


def test_the_published_cell_is_the_accepted_benchmark_extended(cfg):
    bench = manifest.load(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "k-exaone-ep8-cut"
    assert bench["workloads"][-1]["chips"] == 1
    assert cfg.reference == "exaone_moe" and cfg.hf["model_type"] == "exaone_moe"
    assert cfg.raw["reduced"] == bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size"]
    assert cfg.flag("--speculative-mtp") == "1"
    # every width as published
    assert (cfg.hf["hidden_size"], cfg.hf["intermediate_size"],
            cfg.hf["moe_intermediate_size"], cfg.hf["head_dim"],
            cfg.hf["num_attention_heads"], cfg.hf["num_key_value_heads"],
            cfg.hf["num_experts_per_tok"], cfg.hf["sliding_window"]) == (
        6144, 18432, 2048, 128, 64, 8, 8, 128)
    for key in ("qk_norm", "nope_full", "pre_norm", "router_bias", "mtp_form",
                "mtp_concat_order", "mtp_hidden_normed", "mtp_block_sparse",
                "mtp_slots"):
        assert key in cfg.raw["assumed"], key
    with open(os.path.join(manifest.ROOT, "perf/traffic/thinking-closed.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["shared_prefix_tokens"]) == (64, 1024)
    assert mix["pool"] % 64 == 0 and len(mix["check"]) == 24


def test_the_verify_attentions_bytes_at_the_published_widths(cfg):
    c = paged_attn_verify.cost(STEP, cfg.hf, cfg)
    # 4,096 B a token and layer: the full layer and the draft layer read the
    # context, the four window layers their window, once for both positions
    context = 64 * 3700 * 2 + 64 * 128 * 4
    assert c["bytes"] == context * 4096 + 6 * 64 * 2 * 8192 * 2 * 2
    assert c["flops"] == 4.0 * 8192 * context * 2
    # any other step, and a program without the window group: absent
    assert paged_attn_verify.cost(dict(STEP, step=None), cfg.hf, cfg) is None
    assert paged_attn_verify.cost(
        {k: v for k, v in STEP.items() if k != "window_tokens"}, cfg.hf, cfg) is None


def test_the_whole_steps_bytes_are_the_held_weights_and_the_pages(cfg):
    c = mtp_decode_step.cost(STEP, cfg.hf, cfg)
    pages = paged_attn_verify.cost(STEP, cfg.hf, cfg)
    # 4,544 M parameters held: all but the embedding's rows (118 M), the head
    # a second time (118 M), every held expert touched at 128 tokens
    weights = c["bytes"] - pages["bytes"]
    assert 9.05e9 < weights < 9.25e9
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # the bytes decide
    # two rows a step touch a fifth of the held experts: fewer bytes
    few = mtp_decode_step.cost(dict(STEP, rows=2, kv_tokens=2 * 3700,
                                    window_tokens=2 * 128), cfg.hf, cfg)
    assert few["bytes"] < 0.7 * c["bytes"]
    assert mtp_decode_step.cost(dict(STEP, step=None), cfg.hf, cfg) is None


def test_the_step_readers_take_the_costs_and_stay_under_the_roofline(cfg):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    step = dict(STEP, module_s=0.020, ops_s={"^%paged_attn_prefill": 0.004})
    ctx = {"cfg": cfg, "peaks": peaks, "cost_dirs": None,
           "host_trace": {"decode_steps": [step]}}
    mfu = trace_step_module_roofline.read(
        _spec("runner.mtp_decode_step_mfu")["params"], ctx)
    attn = trace_step_roofline.read(
        _spec("kernel.paged_attn_verify_roofline")["params"], ctx)
    assert 50 < mfu < 100 and 50 < attn < 100
    # a program without the step (the parent's): nothing to read, no raise
    empty = dict(ctx, host_trace={"decode_steps": []})
    assert trace_step_module_roofline.read(
        _spec("runner.mtp_decode_step_mfu")["params"], empty) is None


@pytest.mark.parametrize("name,before,after,want", [
    ("spec.mtp_accepted_share",
     {"vllm:spec_decode_num_accepted_tokens_total": 1,
      "vllm:spec_decode_num_draft_tokens_total": 1000},
     {"vllm:spec_decode_num_accepted_tokens_total": 11,
      "vllm:spec_decode_num_draft_tokens_total": 201000}, 0.005),
    ("spec.mtp_tokens_per_row_step",
     {"pst:mtp_tokens_emitted_total": 100, "pst:mtp_row_steps_total": 100},
     {"pst:mtp_tokens_emitted_total": 400, "pst:mtp_row_steps_total": 300}, 1.5),
    ("moe.mtp_top8_held_pair_share",
     {"pst:moe_pairs_held_total": 0, "pst:moe_pairs_routed_total": 0},
     {"pst:moe_pairs_held_total": 128, "pst:moe_pairs_routed_total": 1024}, 12.5),
    ("moe.mtp_top8_experts_touched_share",
     {"pst:moe_experts_touched_total": 0, "pst:moe_layer_steps_total": 0},
     {"pst:moe_experts_touched_total": 80, "pst:moe_layer_steps_total": 5}, 100.0),
    ("moe.mtp_top8_busiest_expert_over_mean",
     {"pst:moe_busiest_expert_pairs_total": 0, "pst:moe_pairs_held_total": 0},
     {"pst:moe_busiest_expert_pairs_total": 14, "pst:moe_pairs_held_total": 128},
     1.75),
    ("kv.mtp_window_resident_share",
     {"pst:window_page_steps_total": 0,
      "pst:window_whole_context_page_steps_total": 0},
     {"pst:window_page_steps_total": 300,
      "pst:window_whole_context_page_steps_total": 3000}, 10.0),
])
def test_counter_metrics_read_their_deltas(name, before, after, want):
    spec = _spec(name)
    assert spec["reader"] == "prom_delta"
    ctx = {"prom_before": {k: [({}, float(v))] for k, v in before.items()},
           "prom_after": {k: [({}, float(v))] for k, v in after.items()}}
    assert prom_delta.read(spec["params"], ctx) == pytest.approx(want)
    # a program without the counters (the parent's): left out, not raised
    assert prom_delta.read(
        spec["params"], {"prom_before": {}, "prom_after": {}}) is None
