"""Operations and bytes of one call of the int4 matmul kernel
(``ops/int4_matmul.py``): ``x [N, K] @ dequant(packed [K/2, M], scales
[K/128, M]) -> f32 [N, M]``, the activations handed over as their even and
odd contraction columns ``[N, K/2]`` each.

The algorithm needs 2 N K M operations on the bf16 unit (the kernel widens
the nibbles to bf16), and reads the packed weights (half a byte each), the
scales, the activations, and writes the result. N is the row count of the
call as traced, which includes the rows the wrapper padded on."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 5:
        return None
    result, xe, xo, packed, scales = sh[0], sh[1], sh[2], sh[3], sh[4]
    if packed[0] != "s8" or len(packed[1]) != 2 or len(xe[1]) != 2:
        return None
    n, half_k = xe[1]
    k, m = 2 * half_k, packed[1][1]
    if packed[1][0] != half_k:
        return None
    per_call = {
        "flops": 2.0 * n * k * m,
        "bytes": (hlo.nbytes(packed) + hlo.nbytes(scales) + hlo.nbytes(xe)
                  + hlo.nbytes(xo) + hlo.nbytes(result)),
    }
    return {"flops": per_call["flops"] * call["count"],
            "bytes": per_call["bytes"] * call["count"],
            "peak": "bf16_flops_per_s"}
