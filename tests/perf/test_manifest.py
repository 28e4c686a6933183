"""``BENCHMARK.json`` against the contract, and against the files it names:
every configuration, traffic mix and per-layer metric is a file of its own
that the harness finds by name.

Every test here states a property and runs twice: on the accepted
benchmark, and on the benchmark as the next PR will leave it (``_later``:
one more configuration and cell, one more kernel metric that lists that
cell and has a cost module of its own, one more generic metric; entries of
``data/BENCHMARK.later.json``, files under ``data/``). A test that fails
on the second alone pins today's contents, and stops a PR that may add
entries and files but may not edit this directory."""

import importlib
import json
import os
import re

import pytest

from perf import config as configs
from perf import cost as costs
from perf import end_to_end, layers, manifest, warmup

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEY = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim|"
                       r"num_experts_per_tok|expansion")


DATA = os.path.join(os.path.dirname(__file__), "data")
# where the later PR's files are found: beside the tests' data, as the
# harness takes them (``run_cell(..., data_dirs=...)``)
LATER_DIRS = {kind: [os.path.join(DATA, kind)]
              for kind in ("traffic", "layer_metrics", "cost", "reference")}


def _later(bench):
    """The benchmark as a later PR leaves it: ``data/BENCHMARK.later.json``'s
    entries appended to the lists of the same name."""
    with open(os.path.join(DATA, "BENCHMARK.later.json")) as f:
        more = json.load(f)
    return dict(bench, **{group: bench[group] + entries
                          for group, entries in more.items()
                          if not group.startswith("_")})


@pytest.fixture(scope="module", params=["accepted", "later"])
def stage(request):
    return request.param


@pytest.fixture(scope="module")
def bench(stage):
    accepted = manifest.load()
    return _later(accepted) if stage == "later" else accepted


@pytest.fixture(scope="module")
def dirs(stage):
    return LATER_DIRS if stage == "later" else {}


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(bench, stage):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    if stage == "accepted":  # the committed file itself
        assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    else:
        assert len(json.dumps(bench, indent=1).encode()) <= 64 * 1024
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


def test_every_cell_finds_its_files_and_reports_enough(bench, dirs):
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
        mix = manifest.load_mix(w["traffic"], dirs.get("traffic"))
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
        if "workloads" in m:  # a list names accepted cells, each once, and some
            assert m["workloads"] and set(m["workloads"]) <= cell_names, m["name"]
            assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_every_per_layer_metric_is_a_file_with_a_reader(bench, dirs):
    for m in bench["per_layer"]:
        spec = manifest.load_layer_metric(m["name"], dirs.get("layer_metrics"))
        # the file says how the number is read and nothing that
        # BENCHMARK.json says already: no cell list, no second copy of a field
        assert set(spec) == {"what", "reader", "params"}, m["name"]
        reader = importlib.import_module(f"perf.readers.{spec['reader']}")
        assert callable(reader.read)
        if spec["reader"] in ("trace_roofline", "trace_step_roofline"):
            assert m["name"].endswith("_roofline") or "_roofline." in m["name"]
            assert m["unit"] == "%"
            # it says which operations are the kernel, and how to cost them
            assert spec["params"]["pattern" if spec["reader"] == "trace_roofline"
                                  else "ops"]
            cost = costs.load(spec["params"]["cost"], dirs.get("cost"))
            assert callable(cost.cost)
    # no file of the benchmark's own is left without an entry, and no entry
    # without a file (the later PR's files lie beside the tests' data)
    on_disk = {f[:-5] for f in os.listdir(os.path.join(manifest.HERE, "layer_metrics"))}
    beside = {f[:-5] for d in dirs.get("layer_metrics", []) for f in os.listdir(d)}
    assert on_disk == {m["name"] for m in bench["per_layer"]} - beside


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


def test_a_cell_reports_the_generic_metrics_and_those_that_list_it(bench, dirs):
    """Every end-to-end metric and every per-layer metric that reads the
    program's generic spans, counters and step programs carries no list and
    applies to every cell, so a later PR's cell is an entry of ``workloads``
    (plus, for a new configuration or mix, files of their own); a metric
    with a list is owed by the cells it names and by no other. Which
    metrics those are, and how many, is the benchmark's to say and no
    test's."""
    assert not any("workloads" in m for m in bench["end_to_end"])
    generic = [m for m in bench["per_layer"] if "workloads" not in m]
    assert generic
    for w in bench["workloads"]:
        name = w["name"]
        assert manifest.metrics_of(bench, "end_to_end", name) == bench["end_to_end"]
        mine = manifest.metrics_of(bench, "per_layer", name)
        assert [m for m in mine if "workloads" not in m] == generic
        assert mine == [m for m in bench["per_layer"]
                        if "workloads" not in m or name in m["workloads"]]
        for m in mine:  # and every reader is found for it as it is
            assert manifest.load_layer_metric(
                m["name"], dirs.get("layer_metrics"))["reader"]


@pytest.mark.parametrize("metric", KERNEL_METRICS)
def test_a_kernel_metric_names_the_cells_whose_kernels_it_reads(bench, metric):
    """Its cost function counts one architecture's and one quantisation's
    work (int4 leaves; keys and values of every layer, read whole): it lists
    the int4 cell, a cell that does not list it owes no such line, and
    nothing else about the entry changed."""
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert entry == {"name": metric, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "itl_p50_ms", "workloads": [INT4_CELL]}
    assert entry in manifest.metrics_of(bench, "per_layer", INT4_CELL)
    for w in bench["workloads"]:
        if w["name"] != INT4_CELL:
            assert entry not in manifest.metrics_of(bench, "per_layer", w["name"])
    spec = manifest.load_layer_metric(metric)
    assert "workloads" not in spec and INT4_CELL not in json.dumps(spec)


def test_what_the_next_pr_brings_is_found_by_name_and_read():
    """The rehearsal itself: on the accepted benchmark plus
    ``data/BENCHMARK.later.json`` the new cell owes the generic metrics (the
    new one among them) and its own kernel metric, not the int4 cell's; the
    int4 cell owes the new generic metric and not the new kernel's; what
    ``host_trace.reduce`` is told to time inside a decode step is what each
    cell's own metrics name; and the harness reads the new kernel metric
    through its own file and its own cost module on a reduction that timed
    its operations, beside the accepted one on the same steps."""
    accepted = manifest.load()
    later, metrics_dir = _later(accepted), LATER_DIRS["layer_metrics"]
    new_cell = manifest.cell(later, "later-moe.later-closed")
    old_cell = manifest.cell(later, INT4_CELL)
    names = {c["name"]: {m["name"] for m in manifest.metrics_of(
        later, "per_layer", c["name"])} for c in (new_cell, old_cell)}
    before = {m["name"] for m in manifest.metrics_of(accepted, "per_layer", INT4_CELL)}
    generic = {m["name"] for m in accepted["per_layer"] if "workloads" not in m}
    assert names[INT4_CELL] == before | {"test.requests_served"}
    assert names[new_cell["name"]] == generic | {
        "test.requests_served", "kernel.later_scan_decode_roofline"}
    assert layers.step_ops(later, new_cell, metrics_dir) == ["^%later_scan_decode"]
    theirs = layers.step_ops(later, old_cell, metrics_dir)
    assert "^%paged_attn_decode" in theirs and "^%later_scan_decode" not in theirs

    cfg = configs.load(new_cell["config_file"])
    step = {"kind": "decode", "bucket": "b8", "rows": 6, "state_slots": 8,
            "new_tokens": 6, "kv_tokens": 600, "kv_pages": 40,
            "module": "jit_pst_decode_step", "module_s": 1e-4,
            "ops_s": {"^%later_scan_decode": 2e-6, "^%paged_attn_decode": 1e-5}}
    host = {"window_s": 1.0, "idle_s": 0.5, "spans": 9, "idle_by_phase": {},
            "modules": {}, "gaps": [], "steps_kept": 1, "clock_violations": 0,
            "decode_steps": [step]}
    ctx = {"host_trace": host, "trace": None, "cfg": cfg, "cell": new_cell,
           "peaks": manifest.load_peaks()["TPU v5 lite"],
           "cost_dirs": LATER_DIRS["cost"], "prom_before": {}, "prom_after": {},
           "spans": {}, "timings": {}, "summary": {}, "window_wall": (0.0, 1.0)}
    only = {"per_layer": [m for m in later["per_layer"]
                          if m["name"].startswith("kernel.")]}
    got = layers.metrics(dict(later, **only), new_cell, ctx, metrics_dir)
    # 8 state slots x 8 heads x 16 x 16 x 2 layers float32 states, read and
    # written once: 262,144 B over 819 GB/s against 2 us measured
    assert set(got) == {"kernel.later_scan_decode_roofline"}
    assert got["kernel.later_scan_decode_roofline"] == {
        "value": pytest.approx(262144 / 819e9 / 2e-6 * 100), "unit": "%"}
    got = layers.metrics(dict(later, **only), old_cell,
                         dict(ctx, cell=old_cell), metrics_dir)
    # the accepted one from the same step; no calls traced for the others
    assert "kernel.paged_attn_decode_roofline" in got
    assert "kernel.later_scan_decode_roofline" not in got


def test_a_new_mix_and_metric_are_found_without_editing_perf():
    mix = manifest.load_mix("tiny-closed", LATER_DIRS["traffic"])
    assert mix["generator"] == "closed_loop"
    spec = manifest.load_layer_metric(
        "test.requests_served", LATER_DIRS["layer_metrics"])
    assert spec["reader"] == "prom_delta"
    with pytest.raises(FileNotFoundError):
        manifest.load_mix("tiny-closed")  # not among the benchmark's own
