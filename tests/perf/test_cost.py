"""The cost functions: hand-computed operations and bytes for one shape each,
and the roofline arithmetic on top of them."""

import pytest

from perf import manifest
from perf.cost import hlo, int4_matmul
from perf.readers import trace_roofline


def test_hlo_shapes_and_bytes():
    text = ("%custom-call.7 = f32[8,14336]{1,0} custom-call(bf16[8,2048]{1,0} %a, "
            "f8e4m3fn[4,128]{1,0} %b, s8[2048,14336]{1,0} %c), custom_call_target=\"tpu_custom_call\"")
    sh = hlo.shapes(text)
    assert sh == [("f32", [8, 14336]), ("bf16", [8, 2048]),
                  ("f8e4m3fn", [4, 128]), ("s8", [2048, 14336])]
    assert [hlo.nbytes(s) for s in sh] == [8 * 14336 * 4, 8 * 2048 * 2, 512, 2048 * 14336]


INT4_DECODE = {"name": "custom-call.7", "count": 1, "seconds": 1.0, "text": (
    "%c = f32[8,14336]{1,0} custom-call(bf16[8,2048]{1,0} %xe, "
                 "bf16[8,2048]{1,0} %xo, s8[2048,14336]{1,0} %p, f32[32,14336]{1,0} %s)")}


def test_int4_matmul_decode_width():
    c = int4_matmul.cost(INT4_DECODE, {}, None)
    # x [8, 4096] @ W [4096, 14336]: 2 * 8 * 4096 * 14336 operations
    assert c["flops"] == 2 * 8 * 4096 * 14336 == 939_524_096
    # packed 2048*14336 B, scales 32*14336*4 B, x 2 * 8*2048*2 B, out 8*14336*4 B
    assert c["bytes"] == 29_360_128 + 1_835_008 + 65_536 + 458_752 == 31_719_424
    assert c["peak"] == "bf16_flops_per_s"


def test_int4_matmul_counts_every_call_of_the_group():
    c = int4_matmul.cost(dict(INT4_DECODE, count=3), {}, None)
    assert c["flops"] == 3 * 939_524_096 and c["bytes"] == 3 * 31_719_424


def test_int4_matmul_without_shapes_gives_nothing():
    assert int4_matmul.cost({"name": "x", "text": "%x = f32[2]{0} add(f32[2]{0} %a)", "count": 1, "seconds": 1}, {}, None) is None


class _Cfg:
    hf = {}


def test_roofline_share_is_least_time_over_measured_time():
    peaks = manifest.load_peaks()["TPU v5 lite"]
    least = max(939_524_096 / 197e12, 31_719_424 / 819e9)  # bandwidth-bound
    assert least == pytest.approx(31_719_424 / 819e9)
    call = dict(INT4_DECODE, seconds=2 * least)
    ctx = {"trace": {"calls": [call, {"name": "fusion.1", "text": "%fusion.1 = f32[2]{0} fusion()",
                                      "count": 1, "seconds": 9.0}]},
           "peaks": peaks, "cfg": _Cfg()}
    share = trace_roofline.read({"pattern": r"^%c = ", "cost": "int4_matmul"}, ctx)
    assert share == pytest.approx(50.0)
    assert trace_roofline.read({"pattern": "^nothing", "cost": "int4_matmul"}, ctx) is None
    assert trace_roofline.read({"pattern": "^%fusion", "cost": "int4_matmul"}, ctx) is None
    assert trace_roofline.read({"pattern": ".", "cost": "int4_matmul"},
                               dict(ctx, trace=None)) is None
