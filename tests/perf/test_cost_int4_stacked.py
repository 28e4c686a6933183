"""The stacked int4 matmul's cost function on instruction texts as the chip's
trace carries them (my chip run, PR 25: operand order li, xe, xo, packed,
scales; every layer's weights in the operand, one layer's read)."""

import pytest

from perf import manifest
from perf.cost import int4_matmul, int4_matmul_stacked
from perf.readers import trace_roofline

W_UP_DECODE = (
    "%int4_matmul_stacked.75 = f32[16,14336]{1,0:T(8,128)S(1)} custom-call("
    "s32[1]{0:T(128)} %dynamic_slice.31, "
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.163, "
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.166, "
    "s8[32,2048,14336]{2,1,0:T(8,128)(4,1)} %get-tuple-element.878, "
    "f32[32,32,14336]{2,1,0:T(8,128)} %get-tuple-element.879), "
    "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={"
    "s32[1]{0}, bf16[16,2048]{1,0}, bf16[16,2048]{1,0}, "
    "s8[32,2048,14336]{2,1,0}, f32[32,32,14336]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
W_DOWN_PREFILL = (
    "%int4_matmul_stacked.76 = f32[256,4096]{1,0:T(8,128)S(1)} custom-call("
    "s32[1]{0:T(128)} %dynamic_slice.31, "
    "bf16[256,7168]{1,0:T(8,128)(2,1)S(1)} %bitcast.152, "
    "bf16[256,7168]{1,0:T(8,128)(2,1)S(1)} %bitcast.153, "
    "s8[32,7168,4096]{2,1,0:T(8,128)(4,1)} %get-tuple-element.882, "
    "f32[32,112,4096]{2,1,0:T(8,128)} %get-tuple-element.883), "
    "custom_call_target=\"tpu_custom_call\"")
# The call before PR 25: a 2-D slice, no layer operand (tests/perf/data).
OLD_2D = (
    "%int4_matmul.75 = f32[16,14336]{1,0:T(8,128)S(1)} custom-call("
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.220, "
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.218, "
    "s8[2048,14336]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.38, "
    "f32[32,14336]{1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.39)")


def _call(text, count=1, seconds=1.0):
    return {"name": text.split(" = ")[0], "text": text, "count": count,
            "seconds": seconds}


def test_decode_width_counts_one_layer_not_the_stack():
    c = int4_matmul_stacked.cost(_call(W_UP_DECODE), {}, None)
    # x [16, 4096] @ W [4096, 14336]
    assert c["flops"] == 2 * 16 * 4096 * 14336 == 1_879_048_192
    # one layer: packed 2048*14336 B, scales 32*14336*4 B; x 2 * 16*2048*2 B,
    # out 16*14336*4 B. The whole stack would be 32 times the first two.
    assert c["bytes"] == 29_360_128 + 1_835_008 + 131_072 + 917_504 == 32_243_712
    assert c["bytes"] < 2048 * 14336 * 2
    assert c["peak"] == "bf16_flops_per_s"
    # The same bytes and operations as the 2-D call on that layer's slice.
    old = int4_matmul.cost(_call(OLD_2D), {}, None)
    assert (c["flops"], c["bytes"]) == (old["flops"], old["bytes"])


def test_prefill_width_and_every_call_of_the_group():
    c = int4_matmul_stacked.cost(_call(W_DOWN_PREFILL, count=3), {}, None)
    assert c["flops"] == 3 * 2 * 256 * 14336 * 4096
    assert c["bytes"] == 3 * (7168 * 4096 + 112 * 4096 * 4
                              + 2 * 256 * 7168 * 2 + 256 * 4096 * 4)


@pytest.mark.parametrize("text", [
    OLD_2D,                                             # no layer operand, 2-D weights
    "%x = f32[2]{0} add(f32[2]{0} %a)",                 # no shapes to speak of
    W_UP_DECODE.replace("s8[32,2048,14336]{2,1,0:T", "s8[32,1024,14336]{2,1,0:T"),  # K/2 disagrees with xe
    W_UP_DECODE.replace("f32[32,32,14336]{2,1,0:T", "f32[16,32,14336]{2,1,0:T"),    # scales of another stack
    W_UP_DECODE.replace("s32[1]{0:T(128)} %dynamic_slice.31, ", ""),               # the layer operand missing
], ids=["old-2d-call", "not-a-call", "k-mismatch", "layers-mismatch", "no-layer"])
def test_anything_else_gives_nothing(text):
    assert int4_matmul_stacked.cost(_call(text), {}, None) is None


class _Cfg:
    hf = {}


def test_metric_file_reads_the_new_name_and_the_old_pattern_does_not():
    peaks = manifest.load_peaks()["TPU v5 lite"]
    least = 32_243_712 / 819e9  # bandwidth-bound at 16 rows
    assert least > 1_879_048_192 / 197e12
    new, old = _call(W_UP_DECODE, seconds=2 * least), _call(OLD_2D, seconds=2 * least)
    spec_new = manifest.load_layer_metric("kernel.int4_matmul_stacked_roofline")
    spec_old = manifest.load_layer_metric("kernel.int4_matmul_roofline")
    assert spec_new["reader"] == "trace_roofline"

    def read(spec, calls):
        return trace_roofline.read(
            spec["params"], {"trace": {"calls": calls}, "peaks": peaks, "cfg": _Cfg()})

    # A trace of stacked calls alone: the new metric reads, the old one
    # finds no call (its pattern does not match the new name).
    assert read(spec_new, [new]) == pytest.approx(50.0)
    assert read(spec_old, [new]) is None
    # The parent's program: the other way round, and nothing raises.
    assert read(spec_new, [old]) is None
    assert read(spec_old, [old]) == pytest.approx(50.0)


WK_2D_DECODE = (
    "%int4_matmul.71 = f32[16,1024]{1,0:T(8,128)S(1)} custom-call("
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.220, "
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %bitcast.218, "
    "s8[2048,1024]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.30, "
    "f32[32,1024]{1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.31)")


def test_a_trace_with_both_calls_gives_each_metric_its_own():
    """This PR's program: six leaves a layer on the stacked call, ``wk`` on
    the 2-D one. Each metric reads its own calls and is not made absent by
    the other's (a matching call that does not parse would do that)."""
    peaks = manifest.load_peaks()["TPU v5 lite"]
    wk_bytes = 2048 * 1024 + 32 * 1024 * 4 + 2 * 16 * 2048 * 2 + 16 * 1024 * 4
    wk = _call(WK_2D_DECODE, count=32, seconds=32 * 4 * wk_bytes / 819e9)
    up = _call(W_UP_DECODE, count=32, seconds=32 * 2 * 32_243_712 / 819e9)
    ctx = {"trace": {"calls": [up, wk]}, "peaks": peaks, "cfg": _Cfg()}
    old = manifest.load_layer_metric("kernel.int4_matmul_roofline")["params"]
    new = manifest.load_layer_metric("kernel.int4_matmul_stacked_roofline")["params"]
    assert trace_roofline.read(old, ctx) == pytest.approx(25.0)
    assert trace_roofline.read(new, ctx) == pytest.approx(50.0)


def test_benchmark_lists_the_metric_for_the_int4_cell():
    bench = manifest.load()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "kernel.int4_matmul_stacked_roofline")
    assert entry == {
        "name": entry["name"], "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "itl_p50_ms",
        # its cost function counts int4 leaves: it lists the int4 cell
        "workloads": ["mistral-7b-int4.sessions-closed"]}
    names = [m["name"] for m in manifest.metrics_of(
        bench, "per_layer", "mistral-7b-int4.sessions-closed")]
    assert "kernel.int4_matmul_stacked_roofline" in names
