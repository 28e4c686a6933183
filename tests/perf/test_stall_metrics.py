"""The per-layer metrics that say what held the step loop off (PR 36), read
by readers that were there: the rehearsal cell on the CPU, traced, prints
the ones that need no chip, and a window without a stall reads 0.0 and not
nothing. The entries are the accepted benchmark's own, laid over the tiny
one; their files are found in ``perf/layer_metrics`` by name."""

import json
import os
import time

from perf import manifest, run
from perf.readers import prom_delta

DATA = os.path.join(os.path.dirname(__file__), "data")
DIRS = {"traffic": [os.path.join(DATA, "traffic")],
        "layer_metrics": [os.path.join(DATA, "layer_metrics")]}
ENV = {"JAX_PLATFORMS": "cpu", "PST_FORCE_PALLAS_INTERPRET": "", "XLA_FLAGS": ""}
CAUSES = ("device", "machine", "interpreter", "host_work")
NEW = ["runner.host_offcpu_mean_ms", "engine.stall_s",
       *(f"engine.stall_{c}_s" for c in CAUSES), "engine.gc_pause_s",
       "device.idle_in_gc_share", "engine.deliver_p50_ms", "engine.deliver_p95_ms"]


def test_the_accepted_benchmark_names_the_ten_and_every_cell_reports_them():
    bench = manifest.load()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(entries)
    for name in NEW:
        assert "workloads" not in entries[name]  # generic: every cell
        spec = manifest.load_layer_metric(name)
        assert set(spec) == {"what", "reader", "params"}
    for cause in CAUSES:
        spec = manifest.load_layer_metric(f"engine.stall_{cause}_s")
        assert spec["params"] == {"counter": "pst_engine_stall_seconds_total",
                                  "labels": {"cause": cause}}
    assert "labels" not in manifest.load_layer_metric("engine.stall_s")["params"]
    idle = [n for n in entries if n.startswith("device.idle_in_")]
    assert len(idle) == 8  # with device.idle_unattributed_share: nine shares


def test_rehearsal_cell_traced_reads_no_stall_as_zero(tmp_path):
    tiny = manifest.load(os.path.join(DATA, "BENCHMARK.tiny.json"))
    accepted = {m["name"]: m for m in manifest.load()["per_layer"]}
    bench = dict(tiny, per_layer=tiny["per_layer"] + [accepted[n] for n in NEW])
    result = run.run_cell(
        "tiny-dense-int4.tiny-closed", 2**31 + 36, 4.0, True,
        out_dir=str(tmp_path), require_chip=False, bench=bench, extra_env=ENV,
        data_dirs=DIRS, t_start=time.monotonic())
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # no chip, no profile: the trace's reader finds nothing and is left out
    assert set(NEW) - set(got) == {"device.idle_in_gc_share"}
    with open(os.path.join(str(tmp_path), "engine.log")) as f:
        stalled = [ln for ln in f if " stall " in ln and "WARNING" in ln]
    by_cause = [got[f"engine.stall_{c}_s"]["value"] for c in CAUSES]
    total = got["engine.stall_s"]["value"]
    # 0.0 and not nothing; a loaded machine may hold the tiny engine off in
    # earnest, and then its log says so
    assert (total == 0.0 and by_cause == [0.0] * 4) or stalled
    assert 0 <= sum(by_cause) <= total + 1e-9
    assert got["engine.gc_pause_s"]["value"] >= 0
    assert got["runner.host_offcpu_mean_ms"]["value"] >= 0
    assert 0 <= got["engine.deliver_p50_ms"]["value"] <= got[
        "engine.deliver_p95_ms"]["value"]
    window = json.load(open(os.path.join(str(tmp_path), "window.json")))
    assert "pst_engine_stall_seconds_total" in window["prom_after"]
    assert "pst_engine_gc_pause_seconds_total" in window["prom_after"]


def test_prom_delta_filters_a_counter_by_cause():
    before = {"pst_engine_stall_seconds_total": [
        ({"cause": "device"}, 1.0), ({"cause": "gc"}, 0.5),
        ({"cause": "machine"}, 0.0)]}
    after = {"pst_engine_stall_seconds_total": [
        ({"cause": "device"}, 3.25), ({"cause": "gc"}, 0.5),
        ({"cause": "machine"}, 1.0)]}
    ctx = {"prom_before": before, "prom_after": after}
    counter = {"counter": "pst_engine_stall_seconds_total"}

    def read(**labels):
        return prom_delta.read(dict(counter, **({"labels": labels} if labels else {})), ctx)

    assert read() == 3.25
    assert read(cause="device") == 2.25 and read(cause="machine") == 1.0
    assert read(cause="gc") == 0.0 and read(cause="interpreter") == 0.0
    # a program without the counter (this PR's parent): nothing, not a fault
    assert prom_delta.read(counter, {"prom_before": {}, "prom_after": {}}) is None
