"""The rehearsal of ``test_rehearsal.py`` where ``correct`` has something to
decide. Two cells whose configuration names a reference module of its own
(``"reference": ...``), kept beside the tests' data and not in
``perf/reference/``: one module agrees with the served path (the
repository's numpy oracle), one is wrong on purpose. And a cell of
``test_rehearsal.py`` with the timed path broken underneath the harness:
the engine child alters every token where it is produced
(``data/broken_engine/sitecustomize.py``)."""

import json
import os
import time

import pytest

from perf import manifest, run

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")],
        "reference": [os.path.join(DATA, "reference")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
BROKEN = dict(ENV, PYTHONPATH=os.pathsep.join(
    [os.path.join(DATA, "broken_engine"), manifest.ROOT]))


@pytest.mark.parametrize("workload, module, env, correct", [
    ("tiny-own-ref.tiny-closed", "tiny_oracle", ENV, True),
    ("tiny-wrong-ref.tiny-closed", "tiny_reversed", ENV, False),
    # sound reference, sound harness, and a served model that is not (the
    # first case serves the same model under the same traffic, unbroken)
    ("tiny-dense-int4.tiny-closed", "mistral", BROKEN, False),
])
def test_correct_is_decided_by_the_named_reference_on_what_was_served(
        workload, module, env, correct, tmp_path, capfd):
    bench = manifest.load(os.path.join(DATA, "BENCHMARK.tiny.json"))
    line = run.run_cell(
        workload, 2**31 + 4242, 3.0, False, out_dir=str(tmp_path),
        require_chip=False, bench=bench, extra_env=env, data_dirs=DIRS,
        t_start=time.monotonic())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is correct
    # every response came whole: the numbers decided, and the run printed
    # each beside its limit, on standard error too
    out, err = capfd.readouterr()
    compared = json.loads(err.strip().splitlines()[-1].split("check: ", 1)[1])
    assert "] check: " in out and compared["incomplete"] == []
    assert compared["positions"] == 32 and compared["thresholds"]["tau"] == 0.5
    assert (compared["max_clear_err"] <= 0.5) is correct
    with open(os.path.join(tmp_path, "reference.log")) as f:
        log = f.read()
    assert f"[reference] {module}: weights ready" in log
    with open(os.path.join(tmp_path, "reference_result.json")) as f:
        rows = json.load(f)["variants"]["none"]
    # both check sequences came back in the common shape, dense: no router
    assert len(rows) == 2 and all(
        set(r) == {"id", "logprobs", "argmax", "gap"} and len(r["logprobs"]) == 16
        and r["gap"] == [float("inf")] * 16 for r in rows)


def test_without_the_tests_directory_the_module_is_not_found(tmp_path):
    """The door is the ``reference`` entry of ``data_dirs``; without it the
    module is looked for in ``perf/reference/`` alone and the run has no
    result (never the default reference in its place)."""
    from perf import config as configs
    from perf.harness import BenchError

    cfg = configs.load(os.path.join(DATA, "configs", "tiny-own-ref.json"))
    parsed = [{"id": "s", "complete": True, "tokens": [5, 6, 7, 8, 9],
               "n_prompt": 4, "want": [[1, 2, 3, 4, 9]]}]
    with pytest.raises(BenchError, match=r"perf/reference/tiny_oracle\.py"):
        run.reference_of(cfg, parsed, ["none"], str(tmp_path), 120, extra_env=ENV)
