"""Operations and bytes of one call of the Mamba-1 prefill kernel
(``ops/selective_scan.py`` ``selective_scan_prefill``): a chunk of positions
a row, each row from its own slot's ``[N, d_inner]`` state and back to it.
Scalar-prefetch operands ``s32[1]`` (layer), ``s32[rows]`` three times
(slots, which rows start from zeros, each row's true length), the pool
``f32[layers, slots, N, d_inner]`` (returned as the second result), ``u``
and ``dt`` ``f32[rows, T, d_inner]``, ``A`` ``f32[N, d_inner]``, ``B`` and
``C`` ``f32[rows, T, N]``, ``D``; the first result is ``y`` ``f32[rows, T,
d_inner]``.

The kernel walks a row's true length and no further, and no shape says what
that was: the program counts it (``pst:prefill_tokens_total`` over
``pst:prefill_bucket_positions_total``, real tokens over ``rows x T`` of the
prefill steps) and the reader hands the window's mean on under ``counted``
as ``real_share``. The algorithm has to read ``u``, ``dt``, ``B`` and ``C``
of every real position and write its ``y``, read and write each row's state
once, and read ``A`` once; a real position costs about 8 operations a state
element (as the decode kernel's). **Memory decides**: a 1,024-position call
moves 63 MB (77 us at the chip's bandwidth) where its 0.67 G operations
would take 3.4 us at the bf16 peak. Those operations are the vector unit's,
whose peak ``perf/peaks.json`` does not have (8 x 128 lanes; at the 1.5 GHz
that the bf16 peak implies for four 128 x 128 matrix units, 1.5e12
operations a second for each instruction a cycle it issues, a number no
public source gives): so the share says how close the call comes to moving
its bytes once, and what holds it off that is the scan's serial walk on the
vector unit."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    real = (call.get("counted") or {}).get("real_share")
    if real is None:
        return None
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 13:
        return None
    y, pool_out, li, slots, keep, lens, pool, u, dt, a, bm, cm, d = sh[:13]
    if (li != ("s32", [1]) or slots[0] != "s32" or len(slots[1]) != 1
            or keep != slots or lens != slots or pool[0] != "f32"
            or len(pool[1]) != 4 or pool_out != pool or u != y or dt != y
            or bm != cm or len(y[1]) != 3 or a[1] != pool[1][2:]):
        return None
    rows, n_state = slots[1][0], pool[1][2]
    if y[1][0] != rows or bm[1] != [rows, y[1][1], n_state]:
        return None
    real = min(max(float(real), 0.0), 1.0)
    state = n_state * pool[1][3]
    moved = (real * (3 * hlo.nbytes(y) + 2 * hlo.nbytes(bm)) + hlo.nbytes(a)
             + hlo.nbytes(d) + 2.0 * rows * state * 4)
    return {"flops": 8.0 * real * rows * y[1][1] * state * call["count"],
            "bytes": moved * call["count"],
            "peak": "bf16_flops_per_s"}
