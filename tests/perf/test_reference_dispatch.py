"""The check's reference is found by name: a configuration file's
``reference`` key names the module, absent it is the Mistral / Mixtral one,
an unknown name is an error; the negative controls are the named module's;
and the Mistral / Mixtral arithmetic, moved behind that interface, gives the
numbers it gave before the move."""

import dataclasses
import json
import os

import pytest

from perf import config as configs
from perf import manifest, reference
from perf.reference import run as child

DATA = os.path.join(os.path.dirname(__file__), "data")
OWN = [os.path.join(DATA, "reference")]


def _config(name):
    return os.path.join(DATA, "configs", f"{name}.json")


@pytest.mark.parametrize("path", [
    "perf/configs/mistral-7b-int4.json", _config("tiny-dense-int4"),
    _config("tiny-moe")])
def test_an_absent_key_means_the_mistral_reference(path):
    cfg = configs.load(path)
    assert "reference" not in cfg.raw and cfg.reference == "mistral"
    module = reference.load(cfg.reference)
    assert module.__name__ == "perf.reference.mistral"
    assert module.VARIANTS == ("none", "no_renorm", "rope_1e4")


def test_the_key_is_the_benchmarks_own_and_never_a_model_key():
    cfg = configs.load(_config("tiny-own-ref"))
    assert cfg.reference == "tiny_oracle" and "reference" not in cfg.hf
    plain = configs.load(_config("tiny-dense-int4"))
    assert cfg.hf == plain.hf  # what the program's reader is given
    assert configs.program_model_config(cfg) == dataclasses.replace(
        configs.program_model_config(plain), name=cfg.name)


def test_a_module_beside_the_tests_data_is_found_before_the_benchmarks_own():
    module = reference.load("tiny_oracle", OWN)
    assert module.__file__ == os.path.join(OWN[0], "tiny_oracle.py")
    assert module.VARIANTS == ("none", "next_id")
    assert reference.find("mistral", OWN) == os.path.join(reference.HERE, "mistral.py")


@pytest.mark.parametrize("name, dirs, error, says", [
    # an unknown name names every file it looked for, and nothing stands in
    ("nope", None, FileNotFoundError, r"perf/reference/nope\.py"),
    ("nope", OWN, FileNotFoundError, r"data/reference/nope\.py.*perf/reference/nope\.py"),
    ("tiny_oracle", None, FileNotFoundError, r"perf/reference/tiny_oracle\.py"),
    ("../reference/mistral", None, ValueError, "not a path"),
    # a file that is there but is no reference (the shared helpers)
    ("weights", None, TypeError, r"lacks \['VARIANTS', 'weights', 'teacher_force'\]"),
])
def test_an_unknown_name_is_an_error_that_names_the_file(name, dirs, error, says):
    with pytest.raises(error, match=says):
        reference.load(name, dirs)


def test_every_configuration_of_the_benchmark_finds_its_reference():
    for c in manifest.load()["configs"]:
        assert reference.find(configs.load(c["file"]).reference)


# -- the negative controls are the named module's ---------------------------

SEQ = {"id": "s", "tokens": [5, 77, 300, 41, 8, 210, 99, 3], "n_prompt": 4,
       "want": [[1, 8, 41, 77, 300], [2, 8, 210, 300, 500], [3, 5, 99, 210, 400],
                [3, 4, 5, 99, 511]]}


def _request(config, variants):
    return {"config_file": _config(config), "variants": variants,
            "reference_dirs": OWN, "sequences": [SEQ]}


def test_calibrates_negative_variants_come_from_the_configurations_module():
    """``calibrate.py --negative <variant>`` hands the name to child 2,
    which looks it up in the configuration's own module."""
    out = child.compute(_request("tiny-own-ref", ["none", "next_id"]),
                        log=lambda *_: None)["variants"]
    good, bad = out["none"][0], out["next_id"][0]
    assert good["gap"] == [float("inf")] * 4 and len(good["argmax"]) == 4
    for p, want in enumerate(SEQ["want"]):
        assert set(good["logprobs"][p]) == {str(t) for t in want}
    # the control moved every log-probability to the next id
    assert bad["argmax"] == [(a + 1) % 512 for a in good["argmax"]]
    assert bad["logprobs"] != good["logprobs"]


@pytest.mark.parametrize("config, variant, has", [
    ("tiny-own-ref", "rope_1e4", r"tiny_oracle\.py has \['none', 'next_id'\]"),
    ("tiny-dense-int4", "next_id",
     r"perf/reference/mistral\.py has \['none', 'no_renorm', 'rope_1e4'\]"),
])
def test_a_variant_of_another_module_is_refused_before_any_work(config, variant, has):
    with pytest.raises(ValueError, match=rf"unknown variant \['{variant}'\].*{has}"):
        child.compute(_request(config, ["none", variant]), log=lambda *_: None)


def test_the_oracle_module_and_the_mistral_module_agree_on_the_same_model():
    """Two references that share no equation, through the one interface."""
    a = child.compute(_request("tiny-own-ref", ["none"]), log=lambda *_: None)
    b = child.compute(_request("tiny-dense-int4", ["none"]), log=lambda *_: None)
    ra, rb = a["variants"]["none"][0], b["variants"]["none"][0]
    assert ra["argmax"] == rb["argmax"]
    for la, lb in zip(ra["logprobs"], rb["logprobs"]):
        assert la == pytest.approx(lb, abs=2e-4)


# -- the moved arithmetic gives the recorded numbers ------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "reference_recorded.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["tiny-dense-int4", "tiny-moe"])
def moved(request, recorded):
    got = child.compute(dict(recorded["request"], config_file=_config(request.param)),
                        log=lambda *_: None)
    return recorded["configs"][request.param], got["variants"]


@pytest.mark.parametrize("variant", ["none", "no_renorm", "rope_1e4"])
def test_the_moved_reference_gives_the_parents_numbers(moved, variant):
    """Recorded from the parent tree before the move (the file's ``_note``),
    a 14-position and a 16-position sequence, the second beyond the first
    padded size. The move changed no operation and no order, and on the
    machine that recorded them the numbers are the same floats; the test
    allows 1e-6 because XLA's CPU backend may split a contraction by the
    number of threads a machine gives it."""
    want, got = moved
    assert [r["id"] for r in got[variant]] == [r["id"] for r in want[variant]]
    for g, w in zip(got[variant], want[variant]):
        assert g["argmax"] == w["argmax"]
        assert g["gap"] == pytest.approx(w["gap"], abs=1e-6)
        for lg, lw in zip(g["logprobs"], w["logprobs"]):
            assert lg == pytest.approx(lw, abs=1e-6)
