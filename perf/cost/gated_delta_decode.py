"""Operations and bytes of one call of the gated-delta-rule decode kernel
(``ops/gated_delta.py`` ``gated_delta_decode``): every row's ``[heads, key,
value]`` float32 state is read from the pool by its slot, one position of ``S
<- exp(g) S``, ``u = beta (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q`` is
applied, and the state is written back in place. The call names the layer,
the slots and which rows start from zeros as scalar-prefetch operands
(``s32[1]``, ``s32[rows]``, ``s32[rows]``) in front of the pool ``f32[layers,
slots, heads, key, value]``, which it returns as its second result; then come
``q`` and ``k`` (``f32[rows, 1, heads x key]``), ``v``, the decay and
``beta`` (``f32[rows, 1, heads x value]``, a head's number repeated over its
lanes); the first result is ``o`` ``f32[rows, 1, heads x value]``.

The algorithm has to read and write each row's state once, whatever the pool
holds: 2 x rows x heads x key x value x 4 bytes; and the row's ``q``, ``k``,
``v`` in and ``o`` out with the two numbers a head. About 8 operations a
state element (the decay's product; a product and an add for ``S^T k``; a
product and an add for the rank-one update; a product and an add for ``S^T
q``; the reductions' share): **memory decides** (a row's 4 MiB take 5.1 us at
the chip's bandwidth, its 4.2 M operations 0.02 us at the bf16 peak; they
are the vector unit's, whose peak ``perf/peaks.json`` does not have). Rows
are the call's rows as traced, padding included (a padding row does the same
work on the scratch slot)."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 11:
        return None
    o, pool_out, li, slots, keep, pool, q, k, v, decay, beta = sh[:11]
    if (li != ("s32", [1]) or slots[0] != "s32" or len(slots[1]) != 1
            or keep != slots or pool[0] != "f32" or len(pool[1]) != 5
            or pool_out != pool or len(o[1]) != 3 or v != o or decay != o
            or beta != o or k != q or q[0] != "f32"):
        return None
    rows = slots[1][0]
    heads, key, value = pool[1][2:]
    if o[1] != [rows, 1, heads * value] or q[1] != [rows, 1, heads * key]:
        return None
    state = heads * key * value  # one row's state, one layer
    small = 2 * hlo.nbytes(q) + 2 * hlo.nbytes(o) + 2 * rows * heads * 4
    return {"flops": 8.0 * rows * state * call["count"],
            "bytes": (2.0 * rows * state * 4 + small) * call["count"],
            "peak": "bf16_flops_per_s"}
