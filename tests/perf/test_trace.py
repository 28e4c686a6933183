"""The reduction from a device trace to busy/idle time, per-operation time
and idle gaps, on small recorded traces with known answers. These traces
carry no ``pst.*`` span, so every gap is ``unattributed`` and named by its
neighbours alone (``test_host_trace.py`` has the spans)."""

import json
import os

import pytest

from perf import host_trace, layers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

# Hand-made, times in ns: the device runs 1.0-2.0 s (a while of 1 s holding
# two body operations of 0.4 s and 0.5 s) and 2.5-3.0 s, inside a program
# ("XLA Modules") that spans 0.5-3.5 s, which is the traced interval; the
# host thread runs on to 4 s.
SMALL = {"planes": [
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["serve", 0.0, 4e9]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 0.5e9, 3e9]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while((s32[]) %t)", 1e9, 1e9],
            ["%fusion.2 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p)", 1.0e9, 0.4e9],
            ["%int4_matmul.3 = f32[8,512]{1,0} custom-call(bf16[8,64]{1,0} %a)", 1.5e9, 0.5e9],
            ["%fusion.2 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p)", 2.5e9, 0.5e9]]}]},
]}


def _gaps(extracted: dict) -> list:
    """``host_trace.reduce``'s gaps of a trace in ``trace.extract``'s form
    (``host_trace.extract`` gives each device plane its ``interval``)."""
    planes = []
    for plane in extracted["planes"]:
        spans = [(s, s + d) for ln in plane["lines"] for _, s, d in ln["events"]]
        planes.append(dict(plane, interval=[min(s for s, _ in spans),
                                            max(e for _, e in spans)]))
    return host_trace.reduce({"planes": planes})["gaps"]


def test_busy_idle_and_window():
    r = trace.reduce(SMALL)
    assert r["device_planes"] == ["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(3.0)  # the device's, not the host's 4 s
    assert r["busy_s"] == pytest.approx(1.5)  # union, the while counted once


def test_per_operation_time_is_self_time():
    r = trace.reduce(SMALL)
    assert r["ops"]["%fusion.2 f32[8,128]"] == pytest.approx(0.9)
    assert r["ops"]["%int4_matmul.3 f32[8,512]"] == pytest.approx(0.5)
    assert r["ops"]["%while.1 s32[]"] == pytest.approx(0.1)  # 1.0 less its body
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    calls = {c["name"]: c for c in r["calls"]}
    assert calls["%fusion.2 f32[8,128]"]["count"] == 2
    assert calls["%fusion.2 f32[8,128]"]["text"].startswith("%fusion.2 = f32[8,128]{1,0} fusion(")


def test_idle_gaps_longest_first_and_named_by_their_neighbours():
    r, gaps = trace.reduce(SMALL), _gaps(SMALL)
    assert [g["seconds"] for g in gaps] == pytest.approx([0.5, 0.5, 0.5])
    names = {g["name"] for g in gaps}
    assert ("unattributed: after %int4_matmul.3 f32[8,512] / "
            "before %fusion.2 f32[8,128]") in names
    assert "unattributed: after start of trace / before %while.1 s32[]" in names
    assert "unattributed: after %fusion.2 f32[8,128] / before end of trace" in names
    assert sum(g["seconds"] for g in gaps) + r["busy_s"] == pytest.approx(3.0)


def test_two_chips_average_busy_and_sum_operations():
    two = json.loads(json.dumps(SMALL))
    second = json.loads(json.dumps(SMALL["planes"][1]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = second["lines"][1]["events"][:3]  # 1.0 s busy
    two["planes"].append(second)
    r = trace.reduce(two)
    assert r["busy_s"] == pytest.approx((1.5 + 1.0) / 2)
    assert r["ops"]["%fusion.2 f32[8,128]"] == pytest.approx(0.9 + 0.4)


def test_a_trace_without_device_operations_has_no_busy_time():
    r = trace.reduce({"planes": [SMALL["planes"][0]]})
    assert r["busy_s"] == 0.0 and r["window_s"] == 0.0 and r["device_planes"] == []


def test_breakdown_has_at_most_ten_entries_each():
    r = trace.reduce(SMALL)
    many = [{"name": f"pst.wait: after a / before b{i}", "seconds": 1.0 / (i + 1)}
            for i in range(25)]
    b = layers.breakdown(r, {"gaps": many})
    assert b["device_ops"][0] == ["%fusion.2 f32[8,128]", pytest.approx(0.9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["pst.wait: after a / before b0", 1.0]
    assert layers.breakdown(r, {"gaps": _gaps(SMALL)})["idle_gaps"][0][1] == 0.5
    # the host trace could not be reduced: no gap is named, none is given
    assert layers.breakdown(r)["idle_gaps"] == []
    assert all(isinstance(s, float) for _, s in b["device_ops"] + b["idle_gaps"])


# -- a slice of a real v5e trace -------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_v5e_slice.json")) as f:
        return json.load(f)


def test_recorded_slice_busy_idle_and_gap(recorded):
    r = trace.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.0143939, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.005743167, rel=1e-6)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    # the gap between two decode steps, while the host prepares the next
    longest = _gaps(recorded)[0]
    assert longest["seconds"] == pytest.approx(0.008647525, rel=1e-6)
    assert longest["name"] == ("unattributed: after %copy-done.1 f32[32000] / "
                               "before %copy-start f32[32000]")


def test_recorded_slice_per_operation_times_and_roofline(recorded):
    from perf import manifest
    from perf.readers import trace_roofline

    r = trace.reduce(recorded)
    assert r["ops"]["%closed_call.13 bf16[16,32,128]"] == pytest.approx(0.002056945, rel=1e-6)
    call = next(c for c in r["calls"] if c["name"] == "%int4_matmul.75 f32[16,14336]")
    assert call["count"] == 6 and call["seconds"] == pytest.approx(0.000535029, rel=1e-6)
    spec = manifest.load_layer_metric("kernel.int4_matmul_roofline")

    class Cfg:
        hf = {}

    share = trace_roofline.read(spec["params"], {
        "trace": r, "peaks": manifest.load_peaks()["TPU v5 lite"], "cfg": Cfg()})
    # 16-row decode calls: bandwidth-bound, a little under half the roofline
    assert 30.0 < share < 60.0
