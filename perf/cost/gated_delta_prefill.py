"""Operations and bytes of one call of the gated-delta-rule prefill kernel
(``ops/gated_delta.py`` ``gated_delta_prefill``): the chunked (WY /
UT-transform) form of arXiv:2412.06464 §3, a run of positions a row, each
row from its own slot's ``[heads, key, value]`` float32 state and back to it.
Scalar-prefetch operands ``s32[1]`` (layer), ``s32[rows]`` three times
(slots, which rows start from zeros, each row's true length), the pool
``f32[layers, slots, heads, key, value]`` (returned as the second result),
``q`` and ``k`` ``f32[rows, T, heads x key]``, ``v`` ``f32[rows, T, heads x
value]``, the log-decay summed from each chunk's start and ``beta``
(``f32[rows, heads, chunks, 1, 128]``: a chunk's numbers in the first lanes);
the first result is ``o`` ``f32[rows, T, heads x value]``.

The kernel walks a row's true length (in whole chunks) and no further, and no
shape says what that was: the program counts it
(``pst:prefill_tokens_total`` over ``pst:prefill_bucket_positions_total``,
real tokens over ``rows x T`` of the prefill steps) and the reader hands the
window's mean on under ``counted`` as ``real_share``. The algorithm has to
read ``q``, ``k``, ``v`` and the two numbers a head of every real position
and write its ``o``, and read and write each row's state once. Operations
are the chunked form's, a chunk of ``C = T / chunks`` positions and a head
(``K`` key lanes, ``V`` value lanes): ``K K^T`` and ``Q K^T`` (``2 C^2 K``
each), the triangular system by doubling (``log2(C) - 1`` squarings and as
many products of ``C x C`` matrices, ``2 C^3`` each), its two applications
(``2 C^2 V``, ``2 C^2 K``), the two products with the carried state and the
state's update (``2 C K V`` each), the in-chunk output (``2 C^2 V``). They
are the matrix unit's and are counted once each at the bf16 peak (the kernel
runs them in float32 at "highest", six bf16 passes a product, which the count
does not multiply: that is the kernel's choice, not the algorithm's need).
**Memory decides** at the published widths: a 1,024-position call moves 71 MB
(87 us at the chip's bandwidth) where its 8.6 G operations take 44 us at the
bf16 peak; the result says which under ``bound``."""

import math

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    real = (call.get("counted") or {}).get("real_share")
    if real is None:
        return None
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 12:
        return None
    o, pool_out, li, slots, keep, lens, pool, q, k, v, g, beta = sh[:12]
    if (li != ("s32", [1]) or slots[0] != "s32" or len(slots[1]) != 1
            or keep != slots or lens != slots or pool[0] != "f32"
            or len(pool[1]) != 5 or pool_out != pool or len(o[1]) != 3
            or v != o or k != q or beta != g or len(g[1]) != 5):
        return None
    rows = slots[1][0]
    heads, key, value = pool[1][2:]
    T, chunks = o[1][1], g[1][2]
    if (o[1] != [rows, T, heads * value] or q[1] != [rows, T, heads * key]
            or g[1][:2] != [rows, heads] or not chunks or T % chunks):
        return None
    C = T // chunks
    real = min(max(float(real), 0.0), 1.0)
    doublings = max(int(math.log2(C)) - 1, 0)
    a_chunk = 2.0 * (2 * C * C * key + 2 * doublings * C ** 3
                     + 2 * C * C * value + C * C * key + 3 * C * key * value)
    flops = real * rows * chunks * heads * a_chunk
    moved = (real * (2 * hlo.nbytes(q) + 2 * hlo.nbytes(o)
                     + 2 * rows * heads * T * 4)
             + 2.0 * rows * heads * key * value * 4)
    return {"flops": flops * call["count"], "bytes": moved * call["count"],
            "peak": "bf16_flops_per_s"}
