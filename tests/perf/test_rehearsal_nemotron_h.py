"""The hybrid model class (state-space + attention + latent-MoE blocks)
through the whole sequence of ``perf/run.py`` on the CPU at a tiny size: its
configuration (``"reference": "nemotron_h"``, an expert-parallel share of 4
of 16 experts), a tiny ``closed_loop`` mix and a benchmark file of its own
(``data/BENCHMARK.hybrid-tiny.json``: the accepted generic metrics and this
PR's, listed for the tiny cells), all found by name. And the new cost
module on a recorded call's shapes. Nothing here is a device number."""

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
# Interpreted kernels make a decode step of the tiny model slow when the
# suite's other workers share the machine: a window long enough that every
# client completes requests in it all the same.
WINDOW_S = 12.0

# One decode step's call as the v5e trace names it (my chip run, PR 31):
# 32 rows, five Mamba layers, 37 slots, 128 heads x 64 x 128 packed two heads
# to a lane tile, 8 groups of 128.
SSM_DECODE_CALL = (
    "%ssm_decode.7 = (f32[32,1,8192]{2,1,0}, f32[5,37,64,128,128]{4,3,2,1,0}) "
    "custom-call(s32[1]{0} %reshape.1, s32[32]{0} %select.3, "
    "f32[5,37,64,128,128]{4,3,2,1,0} %get-tuple-element.9, "
    "f32[32,1,8192]{2,1,0} %fusion.11, f32[32,1,8192]{2,1,0} %fusion.12, "
    "f32[32,1,1024]{2,1,0} %bitcast.4, f32[32,1,1024]{2,1,0} %bitcast.5), "
    'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.hybrid-tiny.json"))


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 3131, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_hybrid_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/nemotron_h.py``, the generic
    metrics read, the expert share's counters read, nothing cached."""
    cell = "hybrid-tiny.hybrid-tiny-closed"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed and "kernel.ssm_decode_roofline" not in got
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "sched.cached_prompt_share", "moe.held_pair_share",
            "moe.busiest_expert_over_mean"} <= set(got)
    assert got["sched.cached_prompt_share"] == 0.0  # matching is off: counted, never hit
    assert got["runner.chained_decode_share"] > 50
    # 4 of 16 experts held: a quarter of the pairs when routing is even
    assert 5 < got["moe.held_pair_share"] < 60
    assert got["moe.busiest_expert_over_mean"] >= 1.0
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] nemotron_h: weights ready" in f.read()
    with open(os.path.join(tmp_path, "reference_result.json")) as f:
        rows = json.load(f)["variants"]["none"]
    # the router's gap came back finite at every position of both sequences
    assert len(rows) == 2 and all(
        len(r["gap"]) == 16 and all(0 <= g < 1 for g in r["gap"]) for r in rows)


def test_hybrid_cell_is_not_correct_against_a_softmax_router(bench, tmp_path, capfd):
    """The same served model; the reference scores by softmax: refused."""
    line = _run(bench, "hybrid-tiny-softmax.hybrid-tiny-closed", False, tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}
    _, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert compared["incomplete"] == [] and compared["max_clear_err"] > 0.05


def test_negative_controls_move_the_reference():
    """Every listed variant changes the log-probabilities of the tiny
    model (``state_bf16`` least): none is a no-op."""
    import numpy as np

    from perf.reference import nemotron_h as ref

    cfg = configs.load(os.path.join(DATA, "configs", "hybrid-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(0)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 40)],
             "n_prompt": 30, "want": [[1]] * 10}]
    base, gap = ref.teacher_force(cfg, params, seqs, "none")[0]
    assert base.shape == (10, 128) and gap.shape == (10,) and (gap >= 0).all()
    moved = {}
    for v in ref.VARIANTS[1:]:
        other, _ = ref.teacher_force(cfg, params, seqs, v)[0]
        moved[v] = float(np.abs(other - base).max())
    assert all(m > 0 for m in moved.values()), moved
    assert moved["state_bf16"] < min(
        moved[v] for v in ("softmax_router", "routed_scale_1", "norm_ungrouped"))


def test_ssm_decode_cost_from_a_recorded_call():
    cost = costs.load("ssm_decode")
    c = cost.cost({"text": SSM_DECODE_CALL, "count": 10}, {}, None)
    state = 64 * 128 * 128  # = 128 heads x 64 x 128
    small = 3 * 32 * 8192 * 4 + 2 * 32 * 1024 * 4
    assert c["bytes"] == 10 * (2 * 32 * state * 4 + small)
    assert c["flops"] == 10 * 6 * 32 * state
    # memory decides: 268 MB a call against 0.2 G operations
    assert c["bytes"] / 819e9 > c["flops"] / 197e12


@pytest.mark.parametrize("text", [
    "%ssm_decode.7 = f32[32,1,8192]{2,1,0} custom-call(f32[32,1,8192]{2,1,0} %a)",
    SSM_DECODE_CALL.replace("s32[32]{0}", "s32[16]{0}"),
    SSM_DECODE_CALL.replace("f32[32,1,1024]{2,1,0} %bitcast.5", "f32[32,1,512]{2,1,0} %b"),
])
def test_ssm_decode_cost_refuses_a_call_it_cannot_read(text):
    assert costs.load("ssm_decode").cost({"text": text, "count": 1}, {}, None) is None


# One decode step's grouped product as the v5e trace names it (my chip run,
# PR 31): 704 pairs padded to 768 rows, latent 1024 -> 2688, a bank of 128.
GMM_CALL = (
    "%gmm.2 = f32[768,2688]{1,0:T(8,128)S(1)} custom-call(s32[]{:T(128)} "
    "%get-tuple-element.636, s32[129]{0:T(256)S(1)} %pad_add_fusion.3, "
    "s32[133]{0:T(256)S(1)} %dynamic_slice.59, s32[133]{0:T(256)S(1)} "
    "%dynamic_slice.61, s32[1]{0:T(128)} %constant.494, "
    "bf16[768,1024]{1,0:T(8,128)(2,1)S(1)} %fusion.21, "
    "bf16[128,1024,2688]{2,1,0:T(8,128)(2,1)} %params__layers____moe____w1___1_.1), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[], '
    "s32[129]{0}, s32[133]{0}, s32[133]{0}, s32[1]{0}, bf16[768,1024]{1,0}, "
    "bf16[128,1024,2688]{2,1,0}}")
COUNTED = {"experts_touched": 70.0, "pairs_held": 176.0}


def test_moe_experts_cost_reads_the_touched_experts_not_the_bank():
    c = costs.load("moe_experts_latent").cost(
        {"text": GMM_CALL, "count": 10, "counted": COUNTED}, {}, None)
    expert = 1024 * 2688 * 2
    assert c["bytes"] == 10 * (70 * expert + 176 * (1024 * 2 + 2688 * 4))
    assert c["flops"] == 10 * 2 * 176 * 1024 * 2688
    assert c["bytes"] < 10 * 128 * expert * 0.56  # a little over half the bank
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # means above what the call can hold are cut to it
    whole = costs.load("moe_experts_latent").cost(
        {"text": GMM_CALL, "count": 1,
         "counted": {"experts_touched": 500.0, "pairs_held": 9e9}}, {}, None)
    assert whole["bytes"] == 128 * expert + 768 * (1024 * 2 + 2688 * 4)


@pytest.mark.parametrize("call", [
    {"text": GMM_CALL, "count": 1},  # a program without the counters
    {"text": GMM_CALL, "count": 1, "counted": {"experts_touched": 70.0}},
    {"text": "%gmm = f32[768,2688]{1,0} custom-call(bf16[768,1024]{1,0} %a)",
     "count": 1, "counted": COUNTED},
    {"text": GMM_CALL.replace("bf16[768,1024]{1,0:T", "bf16[768,512]{1,0:T"),
     "count": 1, "counted": COUNTED},
])
def test_moe_experts_cost_refuses_what_it_cannot_read(call):
    assert costs.load("moe_experts_latent").cost(call, {}, None) is None


def _prom(**totals):
    return {f"pst:{k}_total": [({}, float(v))] for k, v in totals.items()}


def test_counted_roofline_reads_trace_and_counters_together():
    from perf.readers import trace_roofline_counted as reader

    spec = manifest.load_layer_metric("kernel.moe_experts_roofline")
    assert spec["reader"] == "trace_roofline_counted"
    peaks = manifest.load_peaks()["TPU v5 lite"]
    least = (70 * 1024 * 2688 * 2 + 176 * (1024 * 2 + 2688 * 4)) / 819e9
    calls = [{"text": GMM_CALL, "count": 4, "seconds": 4 * 2 * least},
             {"text": "%fusion.1 = f32[2]{0} fusion()", "count": 9, "seconds": 1.0}]
    ctx = {"trace": {"calls": calls}, "peaks": peaks,
           "cfg": configs.load(os.path.join(DATA, "configs", "hybrid-tiny.json")),
           "prom_before": _prom(moe_experts_touched=700, moe_pairs_held=1760,
                                moe_layer_steps=10),
           "prom_after": _prom(moe_experts_touched=7700, moe_pairs_held=19360,
                               moe_layer_steps=110)}
    assert reader.read(spec["params"], ctx) == pytest.approx(50.0)
    # the parent's program has no such counters; a run without a trace none
    assert reader.read(spec["params"], dict(ctx, prom_before={}, prom_after={})) is None
    assert reader.read(spec["params"], dict(ctx, trace=None)) is None
    # a trace without the kernel (another model): absent, not 0
    assert reader.read(spec["params"], dict(ctx, trace={"calls": calls[1:]})) is None


def test_paged_attn_cost_counts_the_attention_blocks_alone():
    hybrid = costs.load("paged_attn_kv_layers")
    hf = {"num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
          "hidden_size": 4096, "num_hidden_layers": 11,
          "hybrid_override_pattern": "MEMEMEM*EME"}
    step = {"rows": 32, "new_tokens": 32, "kv_tokens": 55_000}
    c = hybrid.cost(step, hf, None)
    one = costs.load("paged_attn").cost(step, dict(hf, num_hidden_layers=1), None)
    assert c == one and c["bytes"] == 55_000 * 2 * 2 * 128 * 2 + 32 * 32 * 128 * 4
    assert costs.load("paged_attn").cost(step, hf, None)["bytes"] == 11 * c["bytes"]
    assert hybrid.cost(step, dict(hf, hybrid_override_pattern="MEME"), None) is None
    assert hybrid.cost(step, {k: v for k, v in hf.items()
                              if k != "hybrid_override_pattern"}, None) is None
    spec = manifest.load_layer_metric("kernel.paged_attn_decode_hybrid_roofline")
    assert spec["reader"] == "trace_step_roofline"
    assert spec["params"]["ops"] == manifest.load_layer_metric(
        "kernel.paged_attn_decode_roofline")["params"]["ops"]
