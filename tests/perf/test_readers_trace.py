"""The readers over ``perf/host_trace.py``'s reduction, on hand-made
reductions: each gives the number a person computes from the same table,
and None when there is nothing to read. (``test_readers.py`` has the other
kinds.)"""

import pytest

from perf import config as configs
from perf import host_trace, manifest
from perf.cost import paged_attn
from perf.readers import (trace_idle_by_span, trace_module_time,
                          trace_step_roofline)

from .test_host_trace import recorded_slice

REDUCED = {
    "window_s": 2.0, "idle_s": 0.5, "spans": 900,
    "idle_by_phase": {"wait": 0.2, "launch": 0.25, "postprocess": 0.04,
                      "unattributed": 0.01},
    "modules": {"jit_pst_decode_step": [40, 1.2],
                "jit_pst_decode_burst": [10, 0.5],
                "jit_pst_prefill_step": [4, 0.3],
                "jit__page_get": [7, 0.001]},
    "decode_steps": [
        {"kind": "decode", "bucket": "b16", "rows": 16, "new_tokens": 16,
         "kv_tokens": 91000, "kv_pages": 720, "module": "jit_pst_decode_step",
         "module_s": 0.029,
         "ops_s": {"^%paged_attn_decode": 0.0091, "^%int4_matmul": 0.0118}},
        {"kind": "decode", "bucket": "b16", "rows": 16, "new_tokens": 16,
         "kv_tokens": 91016, "kv_pages": 720, "module": "jit_pst_decode_step",
         "module_s": 0.029,
         "ops_s": {"^%paged_attn_decode": 0.0093, "^%int4_matmul": 0.0}},
    ],
    "steps_kept": 50, "clock_violations": 0,
}
ATTN = {"ops": "^%paged_attn_decode", "cost": "paged_attn"}  # the metric's params
NOTHING = dict(REDUCED, spans=0, idle_by_phase={}, modules={}, decode_steps=[])


@pytest.fixture(scope="module")
def cfg():
    bench = manifest.load()
    return configs.load(manifest.cell(bench, bench["workloads"][0]["name"])["config_file"])


def test_idle_by_span_is_a_share_of_the_traced_interval():
    ctx = {"host_trace": REDUCED}
    assert trace_idle_by_span.read({"span": "wait"}, ctx) == pytest.approx(10.0)
    assert trace_idle_by_span.read({"span": "launch"}, ctx) == pytest.approx(12.5)
    assert trace_idle_by_span.read({"span": "unattributed"}, ctx) == pytest.approx(0.5)
    # a phase the device never idled under reads 0, not absent
    assert trace_idle_by_span.read({"span": "no_work"}, ctx) == 0.0
    total = sum(trace_idle_by_span.read({"span": s}, ctx)
                for s in REDUCED["idle_by_phase"])
    assert total == pytest.approx(REDUCED["idle_s"] / REDUCED["window_s"] * 100)


def test_module_time_is_the_mean_of_the_matching_programs():
    ctx = {"host_trace": REDUCED}
    assert trace_module_time.read({"pattern": "^jit_pst_decode_step"}, ctx) \
        == pytest.approx(30.0)
    assert trace_module_time.read({"pattern": "^jit_pst_prefill_step"}, ctx) \
        == pytest.approx(75.0)
    # a pattern over both decode programs: (1.2 + 0.5) s over 50 programs
    assert trace_module_time.read({"pattern": "^jit_pst_decode"}, ctx) \
        == pytest.approx(34.0)
    assert trace_module_time.read({"pattern": "^jit_step"}, ctx) is None


def test_paged_attn_cost_by_hand(cfg):
    c = paged_attn.cost(REDUCED["decode_steps"][0], cfg.hf, cfg)
    # 91,000 context tokens x 2 (k, v) x 8 KV heads x 128 x 1 B (fp8) x 32
    # layers, plus q and the result: 16 rows x 32 heads x 128 x 2 B x 2
    assert c["bytes"] == 91000 * 2 * 8 * 128 * 32 + 16 * 32 * 128 * 2 * 2 * 32
    assert c["bytes"] == 5_963_776_000 + 8_388_608
    # q.k and p.v: 4 x 32 heads x 128 per row and context token, 32 layers
    assert c["flops"] == 4 * 32 * 128 * 91000 * 32
    assert paged_attn.cost({"rows": 0, "kv_tokens": 5}, cfg.hf, cfg) is None
    # a burst of 4 tokens a row reads contexts that end at kv_tokens
    burst = paged_attn.cost({"rows": 2, "new_tokens": 8, "kv_tokens": 100},
                            cfg.hf, cfg)
    context = (94 + 96 + 98 + 100)
    assert burst["flops"] == 4 * 32 * 128 * context * 32
    # bf16 KV where the deployment does not say fp8
    plain = paged_attn.cost(REDUCED["decode_steps"][0], cfg.hf, None)
    assert plain["bytes"] == 2 * 5_963_776_000 + 8_388_608


def test_step_roofline_sums_least_over_measured(cfg):
    peaks = manifest.load_peaks()["TPU v5 lite"]
    ctx = {"host_trace": REDUCED, "peaks": peaks, "cfg": cfg}
    least = sum(paged_attn.cost(s, cfg.hf, cfg)["bytes"] / 819e9
                for s in REDUCED["decode_steps"])  # memory bound by far
    assert least == pytest.approx(2 * 0.007292, rel=1e-3)
    share = trace_step_roofline.read(ATTN, ctx)
    assert share == pytest.approx(least / (0.0091 + 0.0093) * 100)
    assert 75 < share < 85


def test_step_roofline_divides_by_the_time_of_the_operations_it_names(cfg):
    """``params.ops`` says which device operations are the kernel: another
    pattern over the same steps reads its own time (a step in which none of
    its operations ran adds nothing to either sum), and a pattern the
    reduction was not told to time leaves the metric out."""
    peaks = manifest.load_peaks()["TPU v5 lite"]
    ctx = {"host_trace": REDUCED, "peaks": peaks, "cfg": cfg}
    first = paged_attn.cost(REDUCED["decode_steps"][0], cfg.hf, cfg)["bytes"] / 819e9
    other = trace_step_roofline.read(dict(ATTN, ops="^%int4_matmul"), ctx)
    assert other == pytest.approx(first / 0.0118 * 100)
    assert trace_step_roofline.read(dict(ATTN, ops="^%linear_attn"), ctx) is None
    with pytest.raises(KeyError):  # a metric file without the parameter
        trace_step_roofline.read({"cost": "paged_attn"}, ctx)


def test_the_accepted_metric_reads_the_recorded_slice_as_it_did(cfg):
    """``kernel.paged_attn_decode_roofline`` through its own file, on the
    slice recorded on the chip: the number the reader gave when the kernel's
    name was fixed in ``host_trace.py`` (PR 24-26), to the last digit."""
    spec = manifest.load_layer_metric("kernel.paged_attn_decode_roofline")
    assert spec["reader"] == "trace_step_roofline"
    assert spec["params"] == {"ops": "^%paged_attn_decode", "cost": "paged_attn"}
    peaks = manifest.load_peaks()["TPU v5 lite"]
    reduced = host_trace.reduce(recorded_slice(), (spec["params"]["ops"],))
    ctx = {"host_trace": reduced, "peaks": peaks, "cfg": cfg}
    assert trace_step_roofline.read(spec["params"], ctx) == 71.48665329038714


@pytest.mark.parametrize("reader,params", [
    (trace_idle_by_span, {"span": "wait"}),
    (trace_module_time, {"pattern": "^jit_pst_decode_step"}),
    (trace_step_roofline, ATTN),
])
def test_nothing_to_read_gives_none(reader, params, cfg):
    """A program that writes no pst.* spans and names no program (the
    parent of the PR that brought these), an untraced run, a run whose
    reduction failed: the metric is left out, nothing raises."""
    peaks = manifest.load_peaks()["TPU v5 lite"]
    for host in (NOTHING, None):
        ctx = {"host_trace": host, "peaks": peaks, "cfg": cfg}
        assert reader.read(params, ctx) is None
    assert reader.read(params, {"trace": None, "peaks": peaks, "cfg": cfg}) is None
