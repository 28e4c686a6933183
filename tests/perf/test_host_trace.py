"""``perf/host_trace.py``'s reduction: on a hand-made trace whose every
number a person can follow, and on a slice recorded on the chip
(``data/trace_v5e_host_slice.json``; its ``_note`` says how it was cut from a
``--trace 1`` run of the cell)."""

import json
import os

import pytest

from perf import host_trace, layers

DATA = os.path.join(os.path.dirname(__file__), "data")
ATTN_OPS, INT4_OPS = "^%paged_attn_decode", "^%int4_matmul"  # two metrics' params.ops
ATTN = "%paged_attn_decode.3 = bf16[2,32,128]{2,1,0} custom-call(s32[2,64]{1,0} %t)"


def _span(name, start, end, **stats):
    return ["pst." + name, float(start), float(end - start), stats]


def _step(start, end, kind, info_at, parts, **info):
    """A pst.step with its phases: parts = [(phase, start, end), ...]."""
    out = [_span("step", start, end)]
    for phase, s, e in parts:
        out.append(_span(phase, s, e, **({} if phase == "schedule" else {"kind": kind})))
    out.append(_span("step_info", info_at, info_at + 1, kind=kind, **info))
    return out


def hand_made() -> dict:
    """Times in ns over a traced interval [0, 2000]; see the arithmetic in
    ``test_idle_is_attributed_to_the_innermost_phase``."""
    ops = [["%fusion.1 = f32[2]{0} fusion()", 60.0, 40.0], [ATTN, 100.0, 100.0],
           ["%fusion.1 = f32[2]{0} fusion()", 400.0, 100.0], [ATTN, 500.0, 200.0],
           ["%int4_matmul.7 = f32[2,8]{1,0} custom-call()", 700.0, 100.0],
           ["%fusion.1 = f32[2]{0} fusion()", 1100.0, 100.0], [ATTN, 1200.0, 150.0],
           ["%paged_attn_prefill.5 = bf16[1,64,32,128]{3,2,1,0} custom-call()",
            1600.0, 200.0]]
    modules = [["jit_pst_decode_step(11)", 60.0, 140.0],
               ["jit_pst_decode_step(11)", 400.0, 400.0],
               ["jit_pst_decode_step(11)", 1100.0, 250.0],
               ["jit_pst_prefill_step(12)", 1600.0, 200.0]]
    thread = (
        # cut by the left edge of the traced interval: dropped
        _step(-200, 250, "decode", -160,
              [("launch", -150, -100), ("wait", -100, 230), ("postprocess", 230, 250)],
              bucket="b2", rows=2, new_tokens=2, kv_tokens=998, kv_pages=9)
        + [_span("intake", 250, 300)]
        + _step(300, 900, "decode", 350,
                [("schedule", 300, 320), ("batch_build", 320, 360),
                 ("launch", 360, 390), ("wait", 390, 850), ("postprocess", 850, 900)],
                bucket="b2", rows=2, new_tokens=2, kv_tokens=1000, kv_pages=9)
        + [_span("intake", 900, 950)]
        # its wait closes at 1300, its program ends at 1350: a clock violation;
        # 1480-1500 lies in the step but in no phase
        + _step(950, 1500, "decode", 990,
                [("schedule", 950, 960), ("batch_build", 960, 1000),
                 ("launch", 1000, 1050), ("wait", 1050, 1300),
                 ("postprocess", 1300, 1480)],
                bucket="b2", rows=2, new_tokens=2, kv_tokens=1002, kv_pages=9)
        + [_span("no_work", 1500, 1550), _span("intake", 1550, 1560)]
        + _step(1560, 1900, "prefill", 1580,
                [("schedule", 1560, 1570), ("batch_build", 1570, 1590),
                 ("launch", 1590, 1610), ("wait", 1610, 1850),
                 ("postprocess", 1850, 1900)],
                bucket="b1xt64", rows=1, new_tokens=40, kv_tokens=540, kv_pages=5)
        + [_span("intake", 1900, 1950)])  # and nothing from 1950 to 2000
    other = [_span("wait", 0, 2000)]  # an embedding request's fetch, elsewhere
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": other},
                                        {"name": "python", "events": thread}]},
        {"name": "/device:TPU:0", "interval": [0.0, 2000.0], "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
    ]}


def test_idle_is_attributed_to_the_innermost_phase():
    r = host_trace.reduce(hand_made())
    # busy [60,200] [400,800] [1100,1350] [1600,1800]; idle is the rest of
    # [0,2000]: 60 + 200 + 300 + 250 + 200 ns
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["idle_s"] == pytest.approx(1010e-9)
    want = {
        "wait": 60 + 30 + 10 + 50 + 50 + 50,       # the cut step's wait counts
        "postprocess": 20 + 50 + 130 + 50,
        "intake": 50 + 50 + 10 + 50,
        "schedule": 20 + 10 + 10,
        "batch_build": 40 + 40 + 20,
        "launch": 30 + 50 + 10,
        "no_work": 50,
        "unattributed": 20 + 50,  # in a step but in no phase; under no span
    }
    assert {k: round(v * 1e9, 6) for k, v in r["idle_by_phase"].items()} == want
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    assert r["spans"] == len(hand_made()["planes"][0]["lines"][1]["events"])


def test_idle_stretches_are_cut_at_span_boundaries_and_named_by_span_and_neighbours():
    r = host_trace.reduce(hand_made())
    gaps = {g["name"]: g for g in r["gaps"]}
    decode = "%paged_attn_decode.3 bf16[2,32,128]"
    # the stretch [1350, 1600] between the third decode program and the
    # prefill: postprocess to 1480, nothing to 1500, no_work, intake, ...
    between = f"after {decode} / before %paged_attn_prefill.5 bf16[1,64,32,128]"
    assert r["gaps"][0] == {"name": f"pst.postprocess: {between}",
                            "seconds": pytest.approx(130e-9), "count": 1}
    assert gaps[f"unattributed: {between}"]["seconds"] == pytest.approx(20e-9)
    assert gaps[f"pst.no_work: {between}"]["seconds"] == pytest.approx(50e-9)
    # the same kind of stretch twice, [200, 230] and [390, 400]: one entry
    twice = gaps[f"pst.wait: after {decode} / before %fusion.1 f32[2]"]
    assert (twice["count"], twice["seconds"]) == (2, pytest.approx(40e-9))
    assert gaps["pst.wait: after start of trace / before %fusion.1 f32[2]"][
        "seconds"] == pytest.approx(60e-9)
    assert ("unattributed: after %paged_attn_prefill.5 bf16[1,64,32,128] / "
            "before end of trace") in gaps  # 1950-2000, under no span
    # the stretches are the idle time, and each span's are its share of it
    assert sum(g["seconds"] for g in r["gaps"]) == pytest.approx(r["idle_s"])
    for phase, seconds in r["idle_by_phase"].items():
        span = phase if phase == "unattributed" else f"pst.{phase}"
        assert sum(g["seconds"] for g in r["gaps"]
                   if g["name"].startswith(span + ": ")) == pytest.approx(seconds)
    seconds = [g["seconds"] for g in r["gaps"]]
    assert seconds == sorted(seconds, reverse=True)


def test_steps_cut_by_an_edge_are_dropped_and_the_rest_joined_in_order():
    r = host_trace.reduce(hand_made(), (ATTN_OPS,))
    assert r["steps_kept"] == 3  # the first pst.step began before the interval
    assert r["modules"] == {"jit_pst_decode_step": [3, pytest.approx(790e-9)],
                            "jit_pst_prefill_step": [1, pytest.approx(200e-9)]}
    # the program of the cut step is set aside, so the second program belongs
    # to the first whole step: its attention ran 200 ns of the program's 400
    first, second = r["decode_steps"]
    assert (first["kv_tokens"], first["rows"], first["bucket"]) == (1000, 2, "b2")
    assert first["module"] == "jit_pst_decode_step"
    assert first["module_s"] == pytest.approx(400e-9)
    assert first["ops_s"] == {ATTN_OPS: pytest.approx(200e-9)}
    assert (second["kv_tokens"], second["ops_s"][ATTN_OPS]) == (
        1002, pytest.approx(150e-9))


def test_each_metrics_operations_are_timed_inside_the_same_steps():
    """Two kernels of different names in one step's program, each named by
    a metric of its own (``params.ops``): each pattern reads its own
    operations' time, a step in which none ran reads 0, a pattern asked for
    twice is timed once, and no pattern asked for means no time kept."""
    r = host_trace.reduce(hand_made(), (ATTN_OPS, INT4_OPS, ATTN_OPS))
    first, second = r["decode_steps"]
    # the first whole step's program [400, 800]: fusion 100, attention 200,
    # the int4 matmul 100; the second's [1100, 1350]: fusion 100, attention 150
    assert first["ops_s"] == {ATTN_OPS: pytest.approx(200e-9),
                              INT4_OPS: pytest.approx(100e-9)}
    assert second["ops_s"] == {ATTN_OPS: pytest.approx(150e-9), INT4_OPS: 0.0}
    both = host_trace.reduce(hand_made(), ("^%(paged_attn_decode|int4_matmul)",))
    assert [list(s["ops_s"].values()) for s in both["decode_steps"]] == [
        [pytest.approx(300e-9)], [pytest.approx(150e-9)]]
    # the prefill kernel ran in no decode step's program
    other = host_trace.reduce(hand_made(), ("^%paged_attn_prefill",))
    assert [s["ops_s"] for s in other["decode_steps"]] == [
        {"^%paged_attn_prefill": 0.0}] * 2
    bare = host_trace.reduce(hand_made())
    assert [s["ops_s"] for s in bare["decode_steps"]] == [{}, {}]
    # and nothing else of the reduction depends on what was asked for
    for key in set(r) - {"decode_steps"}:
        assert r[key] == bare[key]


def test_a_program_outside_its_steps_launch_and_wait_is_a_clock_violation():
    assert host_trace.reduce(hand_made())["clock_violations"] == 1
    early = hand_made()
    early["planes"][1]["lines"][0]["events"][3][1] = 1585.0  # before its launch
    assert host_trace.reduce(early)["clock_violations"] == 2
    # a pipelined launch is fetched by a later step: the wait after it in its
    # own step is for the program before, and says nothing of this one
    piped = hand_made()
    for ev in piped["planes"][0]["lines"][1]["events"]:
        if ev[0] == "pst.launch" and ev[1] == 1000.0:
            ev[3]["pipelined"] = 1
    assert host_trace.reduce(piped)["clock_violations"] == 0


def test_a_trace_without_spans_reduces_to_empty_tables():
    """The parent of the PR that brought the spans: device planes only."""
    bare = {"planes": [p for p in hand_made()["planes"] if "interval" in p]}
    bare["planes"][0]["lines"][0]["events"] = [["jit_step(7)", 60.0, 140.0]]
    r = host_trace.reduce(bare)
    assert r["idle_by_phase"] == {} and r["decode_steps"] == [] and r["spans"] == 0
    assert r["gaps"] and all(g["name"].startswith("unattributed: after ")
                             for g in r["gaps"])
    assert r["modules"] == {"jit_step": [1, pytest.approx(140e-9)]}
    assert r["idle_s"] == pytest.approx(1010e-9)
    assert host_trace.reduce({"planes": []})["window_s"] == 0.0


def test_leaf_segments_name_every_moment_by_the_innermost_span():
    segs = host_trace.leaf_segments([(0, 100, "a"), (10, 40, "b"), (20, 30, "c"),
                                     (60, 90, "d"), (200, 300, "e")])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
                    (40, 60, "a"), (60, 90, "d"), (90, 100, "a"), (200, 300, "e")]


def recorded_slice() -> dict:
    with open(os.path.join(DATA, "trace_v5e_host_slice.json")) as f:
        data = json.load(f)
    for plane in data["planes"]:  # the operations' texts are stored once
        for line in plane["lines"]:
            names = line.pop("names", None)
            if names:
                line["events"] = [[names[i], s, d] for i, s, d in line["events"]]
    return data


@pytest.fixture(scope="module")
def recorded():
    return recorded_slice()


def test_recorded_slice_phase_idle_sums_to_total_idle(recorded):
    r = host_trace.reduce(recorded)
    assert r["window_s"] > 0 and 0 < r["idle_s"] < r["window_s"]
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    # as trace.py bounds busy and idle on the same events
    from perf import trace

    t = trace.reduce(recorded)
    assert r["window_s"] == pytest.approx(t["window_s"])
    assert r["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"])
    # on the chip the gaps lie under the phases, almost all of them
    assert r["idle_by_phase"]["unattributed"] < 0.05 * r["idle_s"]
    assert r["clock_violations"] == 0


def test_recorded_slice_steps_and_programs(recorded):
    r = host_trace.reduce(recorded, (ATTN_OPS, INT4_OPS))
    note = recorded["_note"]
    assert r["steps_kept"] == note["steps_whole"] < note["steps_in_slice"]
    assert set(r["modules"]) >= {"jit_pst_decode_step", "jit_pst_prefill_step"}
    assert len(r["decode_steps"]) == note["decode_steps_whole"]
    for step in r["decode_steps"]:
        assert step["rows"] in (15, 16) and step["bucket"] == "b16"
        assert 0 < step["ops_s"][ATTN_OPS] < step["module_s"]
        # the second metric's kernel in the same steps: 7 leaves x 32 layers
        # of int4 matmuls, a third to a half of the program
        assert 0.3 < step["ops_s"][INT4_OPS] / step["module_s"] < 0.5
        assert step["ops_s"][ATTN_OPS] + step["ops_s"][INT4_OPS] < step["module_s"]
        assert step["kv_pages"] * 128 >= step["kv_tokens"] > 15 * 3000
    # the kernel's time as the reduction had it before it was told what to
    # time (``attn_s``, PR 24-26), to the last digit
    assert [s["ops_s"][ATTN_OPS] for s in r["decode_steps"]] == [
        0.01066787, 0.0112277, 0.01122639]
    # the attention share of one step, by hand: its least bytes over the
    # chip's bandwidth against the kernel's measured time
    step = r["decode_steps"][0]
    least = (step["kv_tokens"] * 2 * 8 * 128 * 32
             + step["rows"] * 32 * 128 * 4 * 32) / 819e9
    attn_s = step["ops_s"][ATTN_OPS]
    assert least / attn_s == pytest.approx(note["first_step_attn_share"], rel=1e-6)
    assert 0 < least / attn_s <= 1.0


def test_breakdown_names_the_recorded_gaps_by_what_the_host_was_doing(recorded):
    """On the chip the device idles between one step's last copy to the host
    and the next step's first copy in; which part of the step loop that time
    is under is what the breakdown's names now say."""
    from perf import trace

    r = host_trace.reduce(recorded)
    b = layers.breakdown(trace.reduce(recorded), r)
    assert 1 <= len(b["idle_gaps"]) <= 10
    around = "after %copy-done.1 f32[32000] / before %copy-start f32[32000]"
    names = [name for name, _ in b["idle_gaps"]]
    assert names[:3] == [f"pst.wait: {around}", f"pst.launch: {around}",
                         f"pst.postprocess: {around}"]
    for name, seconds in b["idle_gaps"]:
        span, _, rest = name.partition(": ")
        assert span == "unattributed" or span.startswith("pst.")
        assert rest.startswith("after ") and " / before " in rest
        assert isinstance(seconds, float) and seconds > 0
    assert "pst.step" not in {n.split(": ")[0] for n in names}
    # five whole stretches between steps, each cut at the phases' edges
    by_span = {n.split(": ")[0]: s for n, s in b["idle_gaps"] if n.endswith(around)}
    assert by_span["pst.wait"] == pytest.approx(0.011827138, rel=1e-6)
    assert by_span["pst.launch"] == pytest.approx(0.01101854, rel=1e-6)
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(r["idle_s"], rel=1e-3)
