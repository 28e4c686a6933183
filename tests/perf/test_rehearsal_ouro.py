"""The looped dense stack (the layer stack run ``total_ut_steps`` times over
one set of weights, pass x layers + layer cache slots, the prefix cache)
through the whole sequence of ``perf/run.py`` on the CPU at a tiny size: its
configuration (``"reference": "ouro"``), a tiny closed-loop mix behind a
shared prefix and a benchmark file of its own
(``data/BENCHMARK.ouro-tiny.json``: the accepted generic metrics and this
PR's three, listed for the tiny cells), all found by name. Nothing here is
a device number."""

import json
import os
import time

import numpy as np
import pytest

from perf import config as configs
from perf import manifest, run
from perf.reference import ouro as ref

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")],
        "reference": [os.path.join(DATA, "reference")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
WINDOW_S = 6.0


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.ouro-tiny.json"))


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.load(os.path.join(DATA, "configs", "ouro-tiny.json"))
    return cfg, ref.weights(cfg)


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 4949, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/ouro.py``, a sequence behind
    the shared prefix served through the cache of every pass among the check
    set; the generic metrics read, the loop's counter read under this PR's
    name."""
    cell = "ouro-tiny.ouro-tiny-fewshot"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed
    # no trace: no share of a roofline, of a kernel or of a whole step
    assert not [k for k in got if k.endswith(("_roofline", "_mfu"))]
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "sched.cached_prompt_share",
            "model.layer_passes_per_decode_step"} <= set(got)
    # two passes of three layers a dispatch, in the file's units of 48 layers
    assert got["model.layer_passes_per_decode_step"] == pytest.approx(6 / 48)
    # every request sends the 16 shared tokens again, and they are cached
    assert got["sched.cached_prompt_share"] > 30
    with open(os.path.join(tmp_path, "reference.log")) as f:
        log = f.read()
    assert "[reference] ouro: weights ready" in log
    assert "every position leaves at the last of 2 passes" in log
    with open(os.path.join(tmp_path, "window.json")) as f:
        window = f.read()
    for name in ("pst:decode_layer_passes_total",
                 "pst:prefill_layer_passes_total", "pst:kv_slot_layers"):
        assert name in window, name
    with open(os.path.join(tmp_path, "engine.log")) as f:
        assert "over 6 layers of pages, 3072 B a token" in f.read()


def test_cell_is_not_correct_against_a_stack_run_once(bench, tmp_path, capfd):
    """The same served model; the reference runs the stack once: refused."""
    line = _run(bench, "ouro-tiny-one-pass.ouro-tiny-fewshot", False, tmp_path)
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
    assert gap is None and lps.shape == (n_gen, 128)
    return lps


def test_negative_controls_move_the_reference(tiny):
    """Every listed variant changes the log-probabilities of the tiny
    model: none is a no-op. (The prompt is longer than the tiny
    deployment's 32-token prefill budget, so ``slot_by_layer`` reads back
    across chunks.)"""
    base = _logprobs(tiny, "none")
    moved = {v: float(np.abs(_logprobs(tiny, v) - base).max())
             for v in ref.VARIANTS[1:]}
    assert all(m > 0 for m in moved.values()), moved


@pytest.mark.parametrize("variant", ref.VARIANTS[1:])
def test_a_control_is_refused_by_the_tiny_cells_limits(variant, tiny):
    """Each control, compared as ``perf/check.py`` compares, is past the
    tiny configuration's ``tau`` somewhere in 24 positions."""
    base, other = _logprobs(tiny, "none"), _logprobs(tiny, variant)
    top = np.argsort(base, axis=-1)[:, -5:]  # what a server would report
    err = np.abs(np.take_along_axis(other - base, top, axis=-1)).max()
    assert err > tiny[0].check["tau"], (variant, err)
