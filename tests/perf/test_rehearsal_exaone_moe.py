"""The self-drafting class (window and full attention mixed, sigmoid-routed
expert layers with a shared expert, the multi-token-prediction module
served: every decode step a verify-and-draft step) through the whole
sequence of ``perf/run.py`` on the CPU at a tiny size: its configuration
(``"reference": "exaone_moe"``), a tiny closed-loop mix behind a shared
prefix and a benchmark file of its own (``data/BENCHMARK.exaone-tiny.json``:
the accepted generic metrics and this PR's nine, listed for the tiny cells),
all found by name. Nothing here is a device number."""

import json
import os
import time

import numpy as np
import pytest

from perf import config as configs
from perf import manifest, run
from perf.reference import exaone_moe as ref

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")],
        "reference": [os.path.join(DATA, "reference")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
WINDOW_S = 6.0


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.exaone-tiny.json"))


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.load(os.path.join(DATA, "configs", "exaone-tiny.json"))
    return cfg, ref.weights(cfg)


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 5353, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/exaone_moe.py`` with the check
    sequences served by the verify-and-draft program, one of them behind the
    shared prefix through the cache of both page groups and the draft
    layer's pages; the generic metrics read (the step is a decode step to
    them), the draft's and the dispatch's counters read under this PR's
    names."""
    cell = "exaone-tiny.exaone-tiny-thinking"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed
    assert not [k for k in got if k.endswith(("_roofline", "_mfu"))]
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.host_gap_mean_ms", "runner.compiles_in_window",
            "runner.chained_decode_share", "sched.cached_prompt_share",
            "spec.mtp_accepted_share", "spec.mtp_tokens_per_row_step",
            "moe.mtp_top8_held_pair_share",
            "moe.mtp_top8_experts_touched_share",
            "moe.mtp_top8_busiest_expert_over_mean",
            "kv.mtp_window_resident_share"} <= set(got)
    # every decode dispatch a verify-and-draft step and every one chained;
    # a random draft layer over 128 ids is accepted now and then at most
    assert got["runner.chained_decode_share"] == 100
    assert 1.0 <= got["spec.mtp_tokens_per_row_step"] < 1.2
    assert 0 <= got["spec.mtp_accepted_share"] < 20
    # 4 of 16 experts held: a quarter of the pairs when routing is even
    assert 10 < got["moe.mtp_top8_held_pair_share"] < 45
    assert 0 < got["moe.mtp_top8_experts_touched_share"] <= 4 * 6.25
    assert 0 < got["kv.mtp_window_resident_share"] <= 100
    # every request sends the 32 shared tokens again: cached but for the one
    # position a hit computes again
    assert got["sched.cached_prompt_share"] > 25
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] exaone_moe: weights ready" in f.read()
    with open(os.path.join(tmp_path, "window.json")) as f:
        window = json.load(f)
    total = lambda name: sum(  # noqa: E731
        v for _, v in window["prom_after"][name])
    # since the engine started: every decode dispatch a verify-and-draft step
    # (a chained step in flight at the scrape is dispatched, not yet fetched)
    assert 0 <= total("pst:decode_dispatches_total") - total(
        "pst:mtp_steps_total") <= 1 < total("pst:mtp_steps_total")
    assert total("pst:mtp_tokens_emitted_total") >= total(
        "pst:mtp_row_steps_total") > 0
    assert total("pst:moe_layer_steps_total") % 5 == 0


def test_cell_is_not_correct_against_full_layers_that_are_rotated(
        bench, tmp_path, capfd):
    """The same served model; the reference's full-attention layers carry the
    rotary embedding: refused."""
    line = _run(bench, "exaone-tiny-rope-on-full.exaone-tiny-thinking", False,
                tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}
    _, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert compared["incomplete"] == [] and compared["max_clear_err"] > 0.05


def _logprobs(tiny, variant, n_prompt=96, n_gen=24, seed=1):
    cfg, params = tiny
    rng = np.random.RandomState(seed)
    seqs = [{"id": "s", "tokens": [int(t) for t in rng.randint(3, 128, n_prompt + n_gen)],
             "n_prompt": n_prompt, "want": [[1]] * n_gen}]
    lps, gap = ref.teacher_force(cfg, params, seqs, variant)[0]
    assert gap.shape == (n_gen,) and lps.shape == (n_gen, 128)
    return lps


MAIN_CONTROLS = [v for v in ref.VARIANTS[1:] if not v.startswith("mtp_")]


@pytest.mark.parametrize("variant", [
    v for v in MAIN_CONTROLS if not v.endswith("_fp8")])
def test_an_equation_control_is_refused_by_the_tiny_cells_limits(variant, tiny):
    """Each equation's control, compared as ``perf/check.py`` compares, is
    past the tiny configuration's ``tau`` somewhere in 24 positions."""
    base, other = _logprobs(tiny, "none"), _logprobs(tiny, variant)
    top = np.argsort(base, axis=-1)[:, -5:]  # what a server would report
    err = np.abs(np.take_along_axis(other - base, top, axis=-1)).max()
    assert err > tiny[0].check["tau"], (variant, err)


def test_the_precision_controls_move_the_reference(tiny):
    base = _logprobs(tiny, "none")
    for v in ("weights_fp8", "kv_fp8"):
        assert np.abs(_logprobs(tiny, v) - base).max() > 1e-3, v


def test_the_draft_modules_control_moves_its_logits_and_not_the_main_ones(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(2)
    tokens = [int(t) for t in rng.randint(3, 128, 80)]
    rows = list(range(40, 70))
    base = ref.mtp_logits(cfg, params, tokens, rows)
    other = ref.mtp_logits(cfg, params, tokens, rows, "mtp_hidden_unnormed")
    assert base.shape == (30, 128) and np.abs(other - base).max() > 0.05
    assert np.abs(_logprobs(tiny, "mtp_hidden_unnormed")
                  - _logprobs(tiny, "none")).max() == 0
