"""``BENCHMARK.json`` against the contract, and against the files it names:
every configuration, traffic mix and per-layer metric is a file of its own
that the harness finds by name."""

import importlib
import json
import os
import re

import pytest

from perf import config as configs
from perf import end_to_end, manifest, warmup

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEY = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim|"
                       r"num_experts_per_tok|expansion")


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert any(w.startswith(tuple(bench["paths"])) for w in bench["command"])
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(WIDTH_KEY.search(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_finds_its_files_and_reports_enough(bench):
    cell_names = {w["name"] for w in bench["workloads"]}
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for w in bench["workloads"]:
        cell = manifest.cell(bench, w["name"])
        assert cell["config_file"].startswith(tuple(p + "/" for p in bench["paths"]))
        cfg = configs.load(cell["config_file"])
        assert cfg.name == w["config"]
        mix = manifest.load_mix(w["traffic"])
        gen = importlib.import_module(f"perf.generators.{mix['generator']}")
        plan = gen.plan(mix, 2**31 + 7, float(bench["run_seconds"]),
                        cfg.hf["vocab_size"])
        assert plan["requests"] and mix["check"]
        assert set(mix["warmup"]) <= {"probes", "passes", "seconds"}
        spec = mix["warmup"]["probes"]
        assert warmup.probe_groups(spec, plan, cfg.hf["vocab_size"])
        assert spec["page_tokens"] == int(cfg.flag("--block-size"))
        budget = int(cfg.flag("--max-num-batched-tokens"))
        assert spec["blocker_tokens"] == budget  # one whole prefill step
        assert all(sum(g) <= budget for g in spec["groups"])  # and a group fits one
        longest = max(len(s) for s in plan["sessions"]) if plan["sessions"] else max(
            len(r["prompt"]) for r in plan["requests"])
        assert longest + 4096 < int(cfg.flag("--max-model-len"))
        e2e = {m["name"] for m in manifest.metrics_of(bench, "end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert e2e <= set(end_to_end.COMPUTE)
        layer = manifest.metrics_of(bench, "per_layer", w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (m["name"], w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cell_names
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_every_per_layer_metric_is_a_file_with_a_reader(bench):
    layers_by_name = {}
    for m in bench["per_layer"]:
        spec = manifest.load_layer_metric(m["name"])
        # the file says how the number is read and nothing that
        # BENCHMARK.json says already: no cell list, no second copy of a field
        assert set(spec) == {"what", "reader", "params"}, m["name"]
        reader = importlib.import_module(f"perf.readers.{spec['reader']}")
        assert callable(reader.read)
        if spec["reader"] == "trace_roofline":
            assert m["name"].endswith("_roofline") or "_roofline." in m["name"]
            assert m["unit"] == "%"
            cost = importlib.import_module(f"perf.cost.{spec['params']['cost']}")
            assert callable(cost.cost)
        layers_by_name.setdefault(m["layer"], []).append(m["name"])
    on_disk = {f[:-5] for f in os.listdir(os.path.join(manifest.HERE, "layer_metrics"))}
    assert on_disk == {m["name"] for m in bench["per_layer"]}


def test_configurations_state_their_cut(bench):
    for c in bench["configs"]:
        cfg = configs.load(c["file"])
        raw = cfg.raw
        assert raw["source"] == c["source"] and raw["reduced"] == c["reduced"]
        assert raw["deployment"] and isinstance(raw["assumed"], dict)
        assert {"delta", "tau", "tau_loose", "reason"} <= set(cfg.check)
        assert cfg.check["tau"] < cfg.check["tau_loose"]
        # the file itself says what each cut key was at the source; a width
        # is never among them (test_names_units_and_keys)
        assert set(raw["published"]) == set(raw["reduced"])
        assert all(raw["published"][k] != cfg.hf[k] for k in raw["reduced"])
        # only deployment-defining flags: nothing ROADMAP D3/D4/D9 may delete
        for flag in ("--num-decode-steps", "--adaptive-decode-steps",
                     "--overlap-decode", "--no-overlap-decode", "--attn-impl",
                     "--moe-impl", "--warmup"):
            assert flag not in cfg.engine_flags
        mc = configs.program_model_config(cfg)
        assert mc.num_layers == cfg.hf["num_hidden_layers"]


def test_peaks_table_names_its_source():
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        raw = json.load(f)
    assert "Google Cloud" in raw["_source"]
    v5e = manifest.load_peaks()["TPU v5 lite"]
    assert v5e == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9}


KERNEL_METRICS = ("kernel.int4_matmul_roofline",
                  "kernel.int4_matmul_stacked_roofline",
                  "kernel.paged_attn_decode_roofline")
INT4_CELL = "mistral-7b-int4.sessions-closed"


def _later(bench):
    """The benchmark as a later PR leaves it: one more configuration (not
    int4, other kernels) and a cell of it, as entries and files of its own."""
    return dict(bench,
                configs=bench["configs"] + [{
                    "name": "tiny-moe", "source": "test only", "reduced": [],
                    "file": "tests/perf/data/configs/tiny-moe.json", "why": "test"}],
                workloads=bench["workloads"] + [{
                    "name": "tiny-moe.tiny-closed", "config": "tiny-moe",
                    "traffic": "tiny-closed", "chips": 1, "why": "test"}])


def test_a_new_cell_is_one_entry_and_touches_no_file_that_is_there(bench):
    """Every end-to-end metric and every per-layer metric that reads the
    program's generic spans, counters and step programs applies to every
    cell, so a later PR's cell is an entry of ``workloads`` (plus, for a new
    configuration or mix, files of their own)."""
    assert not any("workloads" in m for m in bench["end_to_end"])
    listed = {m["name"] for m in bench["per_layer"] if "workloads" in m}
    assert listed == set(KERNEL_METRICS)
    data = os.path.join(os.path.dirname(__file__), "data")
    later = _later(bench)
    cell = manifest.cell(later, "tiny-moe.tiny-closed")
    assert configs.load(cell["config_file"]).hf["num_local_experts"] == 4
    assert manifest.load_mix(cell["traffic"], [os.path.join(data, "traffic")])
    assert manifest.metrics_of(later, "end_to_end", cell["name"]) == bench["end_to_end"]
    generic = [m for m in bench["per_layer"] if m["name"] not in KERNEL_METRICS]
    assert manifest.metrics_of(later, "per_layer", cell["name"]) == generic
    for m in generic:  # and every reader is found for it as it is
        assert manifest.load_layer_metric(m["name"])["reader"]


@pytest.mark.parametrize("metric", KERNEL_METRICS)
def test_a_kernel_metric_names_the_cells_whose_kernels_it_reads(bench, metric):
    """Its cost function counts one architecture's and one quantisation's
    work (int4 leaves; keys and values of every layer, read whole): it lists
    the int4 cell, a cell that does not list it owes no such line, and
    nothing else about the entry or its file changed."""
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry == {"name": metric, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "itl_p50_ms", "workloads": [INT4_CELL]}
    later = _later(bench)
    assert entry in manifest.metrics_of(later, "per_layer", INT4_CELL)
    assert entry not in manifest.metrics_of(later, "per_layer", "tiny-moe.tiny-closed")
    spec = manifest.load_layer_metric(metric)
    assert "workloads" not in spec and INT4_CELL not in json.dumps(spec)
    assert len(manifest.metrics_of(bench, "per_layer", INT4_CELL)) == 38


def test_a_new_mix_and_metric_are_found_without_editing_perf():
    data = os.path.join(os.path.dirname(__file__), "data")
    mix = manifest.load_mix("tiny-closed", [os.path.join(data, "traffic")])
    assert mix["generator"] == "closed_loop"
    spec = manifest.load_layer_metric(
        "test.requests_served", [os.path.join(data, "layer_metrics")])
    assert spec["reader"] == "prom_delta"
    with pytest.raises(FileNotFoundError):
        manifest.load_mix("tiny-closed")  # not among the benchmark's own
