"""Shapes out of an HLO instruction's text, which is what the trace names an
operation by: ``%name = f32[8,14336]{1,0} custom-call(bf16[8,2048]{1,0} %a,
s8[2048,14336]{1,0} %b, ...)``. The first shape is the result, the rest the
operands."""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|f8e4m3fn|f8e5m2|f8e4m3|f8e4m3b11fnuz)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def shapes(text: str) -> list:
    """[(dtype, [dims]), ...] in the order they appear."""
    return [(d, [int(x) for x in dims.split(",") if x])
            for d, dims in _SHAPE.findall(text or "")]


def nbytes(shape) -> float:
    dtype, dims = shape
    n = 1
    for d in dims:
        n *= d
    return n * (_BYTES.get(dtype) or 1)  # every f8 kind is one byte
