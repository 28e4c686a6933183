"""The decoder-hybrid-decoder class (Mamba-1, window and full differential
attention, gated memory units, cross-attention) through the whole sequence
of ``perf/run.py`` on the CPU at a tiny size: its configuration
(``"reference": "phi4flash"``), a tiny ``closed_loop`` mix and a benchmark
file of its own (``data/BENCHMARK.phi-tiny.json``: the accepted generic
metrics and this PR's five, listed for the tiny cells), all found by name.
And the three new cost modules on recorded calls' shapes. Nothing here is a
device number.

The cell is sized away from the cliff the latent rehearsal stands at
(ROADMAP R12 (i)): every request is fresh, so no context grows with the
requests a fast machine completes."""

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

# One decode step's calls as the program lowers them at the published widths
# (the shapes of ``ops/selective_scan.py``'s operands: 64 rows, nine Mamba
# layers, 75 slots, 16 states x 5,120 channels).
SCAN_DECODE_CALL = (
    "%selective_scan_decode.3 = (f32[64,1,5120]{2,1,0}, "
    "f32[9,75,16,5120]{3,2,1,0}) custom-call(s32[1]{0} %reshape.1, "
    "s32[64]{0} %select.3, s32[64]{0} %convert.2, "
    "f32[9,75,16,5120]{3,2,1,0} %get-tuple-element.9, "
    "f32[64,1,5120]{2,1,0} %fusion.11, f32[64,1,5120]{2,1,0} %fusion.12, "
    "f32[16,5120]{1,0} %fusion.13, f32[64,16,128]{2,1,0} %broadcast.4, "
    "f32[64,16,128]{2,1,0} %broadcast.5, f32[1,5120]{1,0} %bitcast.6), "
    'custom_call_target="tpu_custom_call"')
SCAN_PREFILL_CALL = (
    "%selective_scan_prefill.2 = (f32[1,1024,5120]{2,1,0}, "
    "f32[9,75,16,5120]{3,2,1,0}) custom-call(s32[1]{0} %reshape.1, "
    "s32[1]{0} %select.3, s32[1]{0} %convert.2, s32[1]{0} %select.4, "
    "f32[9,75,16,5120]{3,2,1,0} %get-tuple-element.9, "
    "f32[1,1024,5120]{2,1,0} %fusion.11, f32[1,1024,5120]{2,1,0} %fusion.12, "
    "f32[16,5120]{1,0} %fusion.13, f32[1,1024,16]{2,1,0} %slice.4, "
    "f32[1,1024,16]{2,1,0} %slice.5, f32[1,5120]{1,0} %bitcast.6), "
    'custom_call_target="tpu_custom_call"')
HF = {"model_type": "phi4flash", "hidden_size": 2560, "num_attention_heads": 40,
      "num_key_value_heads": 20, "num_hidden_layers": 32}


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.phi-tiny.json"))


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 3939, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_phi_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/phi4flash.py``, the generic
    metrics read, the window group's and the cross-decoder's counters read."""
    cell = "phi-tiny.phi-tiny-closed"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed
    assert not [k for k in got if k.endswith("_roofline")]  # no trace, no share
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "kv.window_resident_share",
            "runner.cross_decoder_position_share"} <= set(got)
    # prompts of 8-64 tokens and a window of two pages: a part of the whole
    # context's pages stays, the rest was released
    assert 20 < got["kv.window_resident_share"] < 100
    # one position a prefill row, of 8-32 tokens a row
    assert 1 < got["runner.cross_decoder_position_share"] < 15
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] phi4flash: weights ready" in f.read()
    with open(os.path.join(tmp_path, "window.json")) as f:
        assert "pst:window_pages_released_total" in f.read()


def test_phi_cell_is_not_correct_against_a_lambda_of_zero(bench, tmp_path, capfd):
    """The same served model; the reference never subtracts: refused."""
    line = _run(bench, "phi-tiny-lambda-off.phi-tiny-closed", False, tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}
    _, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert compared["incomplete"] == [] and compared["max_clear_err"] > 0.05


def test_negative_controls_move_the_reference():
    """Every listed variant changes the log-probabilities of the tiny model
    past the window (the precision controls least): none is a no-op."""
    import numpy as np

    from perf.reference import phi4flash as ref

    cfg = configs.load(os.path.join(DATA, "configs", "phi-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(0)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 60)],
             "n_prompt": 50, "want": [[1]] * 10}]
    base, gap = ref.teacher_force(cfg, params, seqs, "none")[0]
    assert base.shape == (10, 128) and gap is None
    moved = {}
    for v in ref.VARIANTS[1:]:
        other, _ = ref.teacher_force(cfg, params, seqs, v)[0]
        moved[v] = float(np.abs(other - base).max())
    assert all(m > 0 for m in moved.values()), moved
    assert max(moved["state_bf16"], moved["kv_fp8"]) < min(
        moved[v] for v in ("window_off", "lambda_off", "memory_stale",
                           "weights_fp8"))


def test_selective_scan_decode_cost_from_a_call():
    c = costs.load("selective_scan_decode").cost(
        {"text": SCAN_DECODE_CALL, "count": 9}, {}, None)
    state = 16 * 5120
    small = 3 * 64 * 5120 * 4 + 16 * 5120 * 4 + 5120 * 4 + 2 * 64 * 16 * 4
    assert c["bytes"] == 9 * (2 * 64 * state * 4 + small)
    assert c["flops"] == 9 * 8 * 64 * state
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides


@pytest.mark.parametrize("real", [1.0, 0.75])
def test_selective_scan_prefill_cost_from_a_call(real):
    """The kernel walks real positions alone: the window's real share of a
    bucket's positions scales what a position costs, not the state's part."""
    cost = costs.load("selective_scan_prefill")
    c = cost.cost({"text": SCAN_PREFILL_CALL, "count": 9,
                   "counted": {"real_share": real}}, {}, None)
    moved = (real * (3 * 1024 * 5120 * 4 + 2 * 1024 * 16 * 4) + 16 * 5120 * 4
             + 5120 * 4 + 2 * 16 * 5120 * 4)
    assert c["bytes"] == 9 * moved
    assert c["flops"] == 9 * 8 * real * 1024 * 16 * 5120
    assert c["bytes"] / 819e9 > c["flops"] / 197e12  # memory decides
    # no count of real positions (a program without the counters): absent
    assert cost.cost({"text": SCAN_PREFILL_CALL, "count": 9}, {}, None) is None


def test_selective_scan_prefill_metric_reads_the_counters():
    from perf.readers import trace_roofline_counted

    spec = manifest.load_layer_metric("kernel.selective_scan_prefill_roofline")
    assert spec["reader"] == "trace_roofline_counted"
    trace = {"calls": [{"text": SCAN_PREFILL_CALL, "count": 9, "seconds": 9 * 425e-6}]}
    ctx = {"trace": trace, "peaks": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
           "cfg": type("C", (), {"hf": {}})(),
           "prom_before": {}, "prom_after": {}}
    assert trace_roofline_counted.read(spec["params"], ctx) is None
    ctx["prom_before"] = {"pst:prefill_tokens_total": [({}, 0.0)],
                          "pst:prefill_bucket_positions_total": [({}, 0.0)]}
    ctx["prom_after"] = {"pst:prefill_tokens_total": [({}, 768.0)],
                         "pst:prefill_bucket_positions_total": [({}, 1024.0)]}
    share = trace_roofline_counted.read(spec["params"], ctx)
    assert 13.0 < share < 14.5  # 0.75 x 63 MB at 819 GB/s over 425 us


@pytest.mark.parametrize("name,text", [
    ("selective_scan_decode",
     "%selective_scan_decode = f32[64,1,5120]{2,1,0} custom-call(f32[4]{0} %a)"),
    ("selective_scan_decode", SCAN_DECODE_CALL.replace("s32[64]{0} %sel", "s32[32]{0} %sel")),
    ("selective_scan_decode", SCAN_DECODE_CALL.replace("f32[16,5120]{1,0}", "f32[8,5120]{1,0}")),
    ("selective_scan_prefill", SCAN_DECODE_CALL),
    ("selective_scan_prefill", SCAN_PREFILL_CALL.replace(
        "f32[1,1024,16]{2,1,0} %slice.5", "f32[1,512,16]{2,1,0} %s")),
])
def test_scan_costs_refuse_a_call_they_cannot_read(name, text):
    call = {"text": text, "count": 1, "counted": {"real_share": 1.0}}
    assert costs.load(name).cost(call, {}, None) is None


def test_shared_kv_attention_cost_from_a_step():
    """64 rows at 2,750 tokens of context: layer 17's pages eight times and
    eight windows of 512, 5,120 bytes a token and layer."""
    step = {"rows": 64, "kv_tokens": 64 * 2750, "window_tokens": 64 * 512,
            "new_tokens": 64}
    c = costs.load("paged_attn_shared_kv").cost(step, HF, None)
    context = (64 * 2750 + 64 * 512) * 8
    assert c["bytes"] == context * 5120 + 2 * 8 * 64 * 40 * 128 * 2 * 2
    assert c["flops"] == 6.0 * 40 * 64 * context
    assert c["bytes"] / 819e9 > c["flops"] / 197e12
    # a program without the window group, another model class: absent
    no_window = {k: v for k, v in step.items() if k != "window_tokens"}
    assert costs.load("paged_attn_shared_kv").cost(no_window, HF, None) is None
    assert costs.load("paged_attn_shared_kv").cost(
        step, dict(HF, model_type="llama"), None) is None


def test_counter_metrics_are_absent_on_a_program_without_the_counters():
    from perf.readers import prom_delta

    for name in ("kv.window_resident_share", "runner.cross_decoder_position_share"):
        spec = manifest.load_layer_metric(name)
        assert spec["reader"] == "prom_delta"
        assert prom_delta.read(
            spec["params"], {"prom_before": {}, "prom_after": {}}) is None
    spec = manifest.load_layer_metric("kv.window_resident_share")
    before = {"pst:window_page_steps_total": [({}, 100.0)],
              "pst:window_whole_context_page_steps_total": [({}, 200.0)]}
    after = {"pst:window_page_steps_total": [({}, 600.0)],
             "pst:window_whole_context_page_steps_total": [({}, 2200.0)]}
    assert prom_delta.read(
        spec["params"], {"prom_before": before, "prom_after": after}) == 25.0
