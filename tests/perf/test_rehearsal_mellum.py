"""The window / full attention mix (periods of window layers closed by a
full layer under YaRN, every layer a softmax router over an expert share,
the prefix cache over both page groups) through the whole sequence of
``perf/run.py`` on the CPU at a tiny size: its configuration (``"reference":
"mellum"``), a tiny closed-loop ``sessions`` mix and a benchmark file of its
own (``data/BENCHMARK.mellum-tiny.json``: the accepted generic metrics and
this PR's seven, listed for the tiny cells), all found by name. Nothing here
is a device number.

The cell is sized away from the cliff the latent rehearsal stands at
(ROADMAP R12 (i)): a session's context grows with every turn a fast machine
completes, so the mix prepares 36 turns of at most 20 tokens in all; were
one session to take every one of them and the check's longest prompt it
would end at 152 + 720 + 116 tokens, under half of ``--max-model-len``
2,048."""

import json
import os
import time

import pytest

from perf import config as configs
from perf import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")],
        "reference": [os.path.join(DATA, "reference")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
WINDOW_S = 8.0


@pytest.fixture(scope="module")
def bench():
    return manifest.load(os.path.join(DATA, "BENCHMARK.mellum-tiny.json"))


def _run(bench, workload, trace, tmp_path):
    return json.loads(json.dumps(run.run_cell(
        workload, 2**31 + 4646, WINDOW_S, trace, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=ENV, data_dirs=DIRS,
        t_start=time.monotonic())))


def test_the_tiny_cell_leaves_its_sessions_twice_the_room_they_can_grow_into():
    cfg = configs.load(os.path.join(DATA, "configs", "mellum-tiny.json"))
    with open(os.path.join(DATA, "traffic", "mellum-tiny-codechat.json")) as f:
        mix = json.load(f)
    longest = mix["shared_prefix_tokens"] + mix["history_tokens"]["hi"]
    a_turn = mix["question_tokens"]["hi"] + mix["output_tokens"]["hi"]
    check = max(c["tokens"] for c in mix["check"]) + 16
    assert 2 * (longest + mix["pool"] * a_turn + check) <= int(
        cfg.flag("--max-model-len"))


def test_cell_whole_run_is_correct_and_reads_its_counters(bench, tmp_path):
    """A traced run (no chip, so no profile: the trace readers leave theirs
    out): ``correct`` against ``perf/reference/mellum.py``, a session turn
    of the check set served through the cache of both page groups among it;
    the generic metrics read, the window group's and the dispatch's counters
    read under this PR's names."""
    cell = "mellum-tiny.mellum-tiny-codechat"
    line = _run(bench, cell, True, tmp_path)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    owed = {m["name"] for m in manifest.metrics_of(bench, "per_layer", cell)}
    assert set(got) <= owed
    assert not [k for k in got if k.endswith("_roofline")]  # no trace, no share
    assert {"client.ttft_p50_ms", "runner.decode_step_mean_ms",
            "runner.compiles_in_window", "runner.chained_decode_share",
            "sched.cached_prompt_share",
            "moe.top8_experts_touched_share", "moe.top8_held_pair_share",
            "moe.top8_busiest_expert_over_mean",
            "kv.mixed_window_resident_share",
            "kv.window_prefix_lost_share"} <= set(got)
    # every turn sends the shared prompt, the history and the turns so far
    # again, and both groups still hold them
    assert got["sched.cached_prompt_share"] > 90
    assert got["kv.window_prefix_lost_share"] < 5
    # contexts of 80-400 tokens under a window of two pages: most of the
    # whole context's pages were given back
    assert 5 < got["kv.mixed_window_resident_share"] < 70
    # 4 of 16 experts held: a quarter of the pairs when routing is even
    assert 10 < got["moe.top8_held_pair_share"] < 45
    # the files' scales are the published cell's (16 held): here 4 are
    assert 0 < got["moe.top8_experts_touched_share"] <= 4 * 6.25
    assert got["moe.top8_busiest_expert_over_mean"] >= 4  # 16 / 4 x (>= 1)
    with open(os.path.join(tmp_path, "reference.log")) as f:
        assert "[reference] mellum: weights ready" in f.read()
    with open(os.path.join(tmp_path, "window.json")) as f:
        window = f.read()
    for name in ("pst:window_pages_cached", "pst:window_prefix_tokens_lost_total",
                 "pst:window_pages_evicted_total", "pst:window_pages_released_total",
                 "pst:moe_pairs_routed_total"):
        assert name in window, name


def test_cell_is_not_correct_against_window_layers_that_see_everything(
        bench, tmp_path, capfd):
    """The same served model; the reference's window layers see the whole
    context: refused."""
    line = _run(bench, "mellum-tiny-window-off.mellum-tiny-codechat", False,
                tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert set(line["metrics"]) == {"out_tok_per_s", "itl_p50_ms", "setup_s"}
    _, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert compared["incomplete"] == [] and compared["max_clear_err"] > 0.05


def test_negative_controls_move_the_reference():
    """Every listed variant changes the log-probabilities of the tiny model
    past the window and past YaRN's original positions: none is a no-op."""
    import numpy as np

    from perf.reference import mellum as ref

    cfg = configs.load(os.path.join(DATA, "configs", "mellum-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(0)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 100)],
             "n_prompt": 90, "want": [[1]] * 10}]
    base, gap = ref.teacher_force(cfg, params, seqs, "none")[0]
    assert base.shape == (10, 128) and gap.shape == (10,) and (gap >= 0).all()
    moved = {}
    for v in ref.VARIANTS[1:]:
        other, _ = ref.teacher_force(cfg, params, seqs, v)[0]
        moved[v] = float(np.abs(other - base).max())
    assert all(m > 0 for m in moved.values()), moved


@pytest.mark.parametrize("variant", [
    "window_off", "yarn_off", "yarn_scale_off", "qk_norm_off", "renorm_off",
    "router_sigmoid"])
def test_an_equation_control_is_refused_by_the_tiny_cells_limits(variant):
    """Each equation's control, compared as ``perf/check.py`` compares, is
    past the tiny configuration's ``tau`` somewhere in 24 positions."""
    import numpy as np

    from perf.reference import mellum as ref

    cfg = configs.load(os.path.join(DATA, "configs", "mellum-tiny.json"))
    params = ref.weights(cfg)
    rng = np.random.RandomState(1)
    seqs = [{"tokens": [int(t) for t in rng.randint(3, 128, 120)],
             "n_prompt": 96, "want": [[1]] * 24}]
    base, _ = ref.teacher_force(cfg, params, seqs, "none")[0]
    other, _ = ref.teacher_force(cfg, params, seqs, variant)[0]
    top = np.argsort(base, axis=-1)[:, -5:]  # what a server would report
    err = np.abs(np.take_along_axis(other - base, top, axis=-1)).max()
    assert err > cfg.check["tau"], (variant, err)
