"""The gated-delta-rule hybrid class (Gated DeltaNet layers on matrix-state
slots, gated attention, a softmax router over an expert share) through the
whole sequence of ``perf/run.py`` on the CPU at a tiny size: its
configuration (``"reference": "qwen3_next"``), a tiny ``closed_loop`` mix
and a benchmark file of its own (``data/BENCHMARK.qwen3next-tiny.json``: the
accepted generic metrics and this PR's seven, listed for the tiny cells),
all found by name. And the three new cost modules on recorded calls' shapes.
Nothing here is a device number.

Every request is fresh, so no context grows with the requests a fast machine
completes (ROADMAP R12 (i))."""

import json
import os
import time

import pytest

from perf import config as configs
from perf import cost as costs
from perf import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")],
        "reference": [os.path.join(DATA, "reference")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
WINDOW_S = 8.0

# The two kernels' calls as the program lowers them at the published widths
# (the shapes of ``ops/gated_delta.py``'s operands: twelve DeltaNet layers,
# 73 slots, 32 heads of a 128 x 128 state; 64 decode rows; one prefill row of
# 1,024 positions in 16 chunks).
DECODE_CALL = (
    "%gated_delta_decode.3 = (f32[64,1,4096]{2,1,0}, "
    "f32[12,73,32,128,128]{4,3,2,1,0}) custom-call(s32[1]{0} %reshape.1, "
    "s32[64]{0} %select.3, s32[64]{0} %convert.2, "
    "f32[12,73,32,128,128]{4,3,2,1,0} %get-tuple-element.9, "
    "f32[64,1,4096]{2,1,0} %fusion.11, f32[64,1,4096]{2,1,0} %fusion.12, "
    "f32[64,1,4096]{2,1,0} %fusion.13, f32[64,1,4096]{2,1,0} %fusion.14, "
    "f32[64,1,4096]{2,1,0} %fusion.15), "
    'custom_call_target="tpu_custom_call"')
PREFILL_CALL = (
    "%gated_delta_prefill.2 = (f32[1,1024,4096]{2,1,0}, "
    "f32[12,73,32,128,128]{4,3,2,1,0}) custom-call(s32[1]{0} %reshape.1, "
    "s32[1]{0} %select.3, s32[1]{0} %convert.2, s32[1]{0} %select.4, "
    "f32[12,73,32,128,128]{4,3,2,1,0} %get-tuple-element.9, "
    "f32[1,1024,4096]{2,1,0} %fusion.11, f32[1,1024,4096]{2,1,0} %fusion.12, "
    "f32[1,1024,4096]{2,1,0} %fusion.13, f32[1,32,16,1,128]{4,3,2,1,0} %pad.4, "
    "f32[1,32,16,1,128]{4,3,2,1,0} %pad.5), "
    'custom_call_target="tpu_custom_call"')
HF = {"model_type": "qwen3_next", "hidden_size": 2048, "head_dim": 256,
      "num_attention_heads": 16, "num_key_value_heads": 2,
      "num_hidden_layers": 16, "full_attention_interval": 4}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.qwen3next-tiny.json"))


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 4141, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/qwen3_next.py``, the generic
    metrics read, the dispatch's counters read under the accepted names."""
    cell = "qwen3next-tiny.qwen3next-tiny-closed"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed
    assert not [k for k in got if k.endswith("_roofline")]  # no trace, no share
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "moe.top10_experts_touched_share", "moe.top10_held_pair_share",
            "moe.top10_busiest_expert_over_mean"} <= set(got)
    # 4 of 16 experts held: a quarter of the pairs when routing is even
    assert 10 < got["moe.top10_held_pair_share"] < 45
    # the files' scales are the published cell's (64 held): here 4 are
    assert 0 < got["moe.top10_experts_touched_share"] <= 4 * 1.5625
    assert got["moe.top10_busiest_expert_over_mean"] >= 16  # 64 / 4 x (>= 1)
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] qwen3_next: weights ready" in f.read()
    with open(os.path.join(tmp_path, "window.json")) as f:
        window = f.read()
    assert "pst:prefill_bucket_positions_total" in window
    assert "pst:state_slots_in_use" in window


def test_cell_is_not_correct_against_a_delta_rule_that_never_decays(
        bench, tmp_path, capfd):
    """The same served model; the reference's state never decays: refused."""
    line = _run(bench, "qwen3next-tiny-decay-off.qwen3next-tiny-closed", False,
                tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}
    _, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert compared["incomplete"] == [] and compared["max_clear_err"] > 0.05


def test_negative_controls_move_the_reference():
    """Every listed variant changes the log-probabilities of the tiny model
    (the precision controls least): none is a no-op."""
    import numpy as np

    from perf.reference import qwen3_next as ref

    cfg = configs.load(os.path.join(DATA, "configs", "qwen3next-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(0)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 60)],
             "n_prompt": 50, "want": [[1]] * 10}]
    base, gap = ref.teacher_force(cfg, params, seqs, "none")[0]
    assert base.shape == (10, 128) and gap.shape == (10,) and (gap >= 0).all()
    moved = {}
    for v in ref.VARIANTS[1:]:
        other, _ = ref.teacher_force(cfg, params, seqs, v)[0]
        moved[v] = float(np.abs(other - base).max())
    # unnormalised keys make the delta rule diverge: nothing finite is left
    assert not np.isfinite(moved.pop("qk_l2norm_off"))
    # (no order among them: a rounding of the state flips an expert of the
    # top 3 of 16 somewhere in eight layers, and a flip moves as much as a
    # wrong equation does)
    assert all(m > 0 for m in moved.values()), moved


@pytest.mark.parametrize("variant", [
    "decay_off", "beta_one", "out_gate_off", "rotary_full", "norm_plain",
    "sigmoid_router", "shared_gate_off"])
def test_an_equation_control_is_refused_by_the_tiny_cells_limits(variant):
    """Each equation's control, compared as ``perf/check.py`` compares, is
    past the tiny configuration's ``tau`` somewhere in 24 positions."""
    import numpy as np

    from perf.reference import qwen3_next as ref

    cfg = configs.load(os.path.join(DATA, "configs", "qwen3next-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(1)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 64)],
             "n_prompt": 40, "want": [[1]] * 24}]
    base, _ = ref.teacher_force(cfg, params, seqs, "none")[0]
    other, _ = ref.teacher_force(cfg, params, seqs, variant)[0]
    top = np.argsort(base, axis=-1)[:, -5:]  # what a server would report
    err = np.abs(np.take_along_axis(other - base, top, axis=-1)).max()
    assert err > cfg.check["tau"], (variant, err)


# ----------------------------------------------------------------------------
# The cost modules
# ----------------------------------------------------------------------------


def test_gated_delta_decode_cost_from_a_call():
    c = costs.load("gated_delta_decode").cost(
        {"text": DECODE_CALL, "count": 12}, {}, None)
    state = 32 * 128 * 128
    # q, k, v in and o out; the decay and beta as the two numbers a head
    small = 4 * 64 * 4096 * 4 + 2 * 64 * 32 * 4
    assert c["bytes"] == 12 * (2 * 64 * state * 4 + small)
    assert c["flops"] == 12 * 8 * 64 * state
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # a step's twelve calls: 3.2 GB, 3.9 ms at the chip's bandwidth
    assert 3.2e9 < c["bytes"] < 3.3e9


@pytest.mark.parametrize("real", [1.0, 0.75])
def test_gated_delta_prefill_cost_from_a_call(real):
    """The kernel walks real positions alone: the window's real share of a
    bucket's positions scales what a position costs, not the state's part."""
    cost = costs.load("gated_delta_prefill")
    c = cost.cost({"text": PREFILL_CALL, "count": 12,
                   "counted": {"real_share": real}}, {}, None)
    state = 2 * 32 * 128 * 128 * 4
    moved = real * (4 * 1024 * 4096 * 4 + 2 * 32 * 1024 * 4) + state
    assert c["bytes"] == 12 * moved
    # a chunk of 64 and a head: 16.8 M operations, 16 x 32 of them a call
    a_chunk = 2 * (2 * 64 * 64 * 128 + 2 * 5 * 64 ** 3 + 2 * 64 * 64 * 128
                   + 64 * 64 * 128 + 3 * 64 * 128 * 128)
    assert a_chunk == 16_777_216
    assert c["flops"] == 12 * real * 16 * 32 * a_chunk
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # no count of real positions (a program without the counters): absent
    assert cost.cost({"text": PREFILL_CALL, "count": 12}, {}, None) is None


def test_gated_delta_prefill_metric_reads_the_counters():
    from perf.readers import trace_roofline_counted

    spec = manifest.load_layer_metric("kernel.gated_delta_prefill_roofline")
    assert spec["reader"] == "trace_roofline_counted"
    trace = {"calls": [{"text": PREFILL_CALL, "count": 12, "seconds": 12 * 1e-3}]}
    ctx = {"trace": trace, "peaks": PEAKS, "cfg": type("C", (), {"hf": {}})(),
           "prom_before": {}, "prom_after": {}}
    assert trace_roofline_counted.read(spec["params"], ctx) is None
    ctx["prom_before"] = {"pst:prefill_tokens_total": [({}, 0.0)],
                          "pst:prefill_bucket_positions_total": [({}, 0.0)]}
    ctx["prom_after"] = {"pst:prefill_tokens_total": [({}, 768.0)],
                         "pst:prefill_bucket_positions_total": [({}, 1024.0)]}
    share = trace_roofline_counted.read(spec["params"], ctx)
    assert 6.5 < share < 7.0  # (0.75 x 67 + 4) MB at 819 GB/s over 1 ms


def test_gated_delta_decode_metric_reads_the_trace():
    from perf.readers import trace_roofline

    spec = manifest.load_layer_metric("kernel.gated_delta_decode_roofline")
    assert spec["reader"] == "trace_roofline"
    ctx = {"trace": {"calls": [
        {"text": DECODE_CALL, "count": 12, "seconds": 12 * 656e-6}]},
        "peaks": PEAKS, "cfg": type("C", (), {"hf": {}})()}
    share = trace_roofline.read(spec["params"], ctx)
    assert 50.0 < share < 51.0  # 270 MB a call at 819 GB/s over 656 us
    # the parent's trace has no such call: the metric is left out
    ctx["trace"] = {"calls": [{"text": "%fusion.1 = f32[2]{0} fusion()",
                               "count": 1, "seconds": 1.0}]}
    assert trace_roofline.read(spec["params"], ctx) is None


@pytest.mark.parametrize("name,text", [
    ("gated_delta_decode",
     "%gated_delta_decode = f32[64,1,4096]{2,1,0} custom-call(f32[4]{0} %a)"),
    ("gated_delta_decode", DECODE_CALL.replace("s32[64]{0} %sel", "s32[32]{0} %sel")),
    ("gated_delta_decode", DECODE_CALL.replace(
        "f32[64,1,4096]{2,1,0} %fusion.11", "f32[64,1,2048]{2,1,0} %f")),
    ("gated_delta_decode", PREFILL_CALL),
    ("gated_delta_prefill", DECODE_CALL),
    ("gated_delta_prefill", PREFILL_CALL.replace(
        "f32[1,32,16,1,128]{4,3,2,1,0} %pad.5", "f32[1,32,8,1,128]{4,3,2,1,0} %p")),
    ("gated_delta_prefill", PREFILL_CALL.replace("[1,32,16,1,128]", "[1,32,15,1,128]")),
])
def test_delta_costs_refuse_a_call_they_cannot_read(name, text):
    call = {"text": text, "count": 1, "counted": {"real_share": 1.0}}
    assert costs.load(name).cost(call, {}, None) is None


def test_interval_layers_attention_cost_from_a_step():
    """64 rows at 1,640 tokens of context: four of sixteen layers hold pages,
    2 x 2 x 256 x 2 bytes a token and layer."""
    step = {"rows": 64, "kv_tokens": 64 * 1640, "new_tokens": 64}
    c = costs.load("paged_attn_interval_layers").cost(step, HF, None)
    context = 64 * 1640
    assert c["bytes"] == (context * 2048 + 64 * 16 * 256 * 2 * 2) * 4
    assert c["flops"] == 4.0 * 16 * 256 * context * 4
    assert c["bytes"] / 819e9 > c["flops"] / 197e12
    # a model without the interval (another class): absent
    plain = {k: v for k, v in HF.items() if k != "full_attention_interval"}
    assert costs.load("paged_attn_interval_layers").cost(step, plain, None) is None
    assert costs.load("paged_attn_interval_layers").cost({"rows": 64}, HF, None) is None


def test_the_expert_products_are_costed_by_the_accepted_module():
    """The banks as this class hands them to ``%gmm``: the 16 layers' 64
    experts seen as one bank of 1,024, gate and up one product."""
    spec = manifest.load_layer_metric("kernel.moe_experts_top10_roofline")
    assert spec["params"]["cost"] == "moe_experts_latent"
    call = {"text": ("%gmm.5 = f32[640,1024]{1,0} custom-call(s32[1024]{0} %a, "
                     "bf16[640,2048]{1,0} %x, bf16[1024,2048,1024]{2,1,0} %w)"),
            "count": 16, "counted": {"experts_touched": 46.0, "pairs_held": 80.0}}
    c = costs.load("moe_experts_latent").cost(call, {}, None)
    assert c["bytes"] == 16 * (46 * 2048 * 1024 * 2 + 80 * (2048 * 2 + 1024 * 4))
    assert c["flops"] == 16 * 2 * 80 * 2048 * 1024


def test_counter_metrics_are_absent_on_a_program_without_the_counters():
    from perf.readers import prom_delta

    names = ("moe.top10_experts_touched_share", "moe.top10_held_pair_share",
             "moe.top10_busiest_expert_over_mean")
    for name in names:
        spec = manifest.load_layer_metric(name)
        assert spec["reader"] == "prom_delta"
        assert prom_delta.read(
            spec["params"], {"prom_before": {}, "prom_after": {}}) is None
    spec = manifest.load_layer_metric("moe.top10_held_pair_share")
    before = {"pst:moe_pairs_held_total": [({}, 100.0)],
              "pst:moe_pairs_routed_total": [({}, 200.0)]}
    after = {"pst:moe_pairs_held_total": [({}, 600.0)],
             "pst:moe_pairs_routed_total": [({}, 4200.0)]}
    assert prom_delta.read(
        spec["params"], {"prom_before": before, "prom_after": after}) == 12.5


def test_the_published_cell_is_the_accepted_benchmark_extended():
    """The new cell's files are found by name from the repository's own
    ``BENCHMARK.json``, its seven metrics list it and nothing else does."""
    bench = manifest.load(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    cell = "qwen3-next-ep8-cut.assist-closed"
    listed = [m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [])]
    assert sorted(listed) == sorted([
        "kernel.gated_delta_decode_roofline", "kernel.gated_delta_prefill_roofline",
        "kernel.moe_experts_top10_roofline", "kernel.paged_attn_decode_hd256_roofline",
        "moe.top10_experts_touched_share", "moe.top10_held_pair_share",
        "moe.top10_busiest_expert_over_mean"])
    for name in listed:
        spec = manifest.load_layer_metric(name)
        if "cost" in spec["params"]:
            assert hasattr(costs.load(spec["params"]["cost"]), "cost")
    cfg = configs.load("perf/configs/qwen3-next-ep8-cut.json")
    assert cfg.reference == "qwen3_next" and cfg.hf["model_type"] == "qwen3_next"
    assert cfg.raw["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg.raw["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
