"""Operations and bytes of one call of the state-space decode kernel
(``ops/ssm.py`` ``ssm_decode``): every row's recurrent state is read from the
pool by its slot, one step of ``S <- decay S + dt x (x) B``, ``y = S C`` is
applied, and the state is written back in place. The call names the layer and
the slots as scalar-prefetch operands (``s32[1]``, ``s32[rows]``) in front of
the pool ``f32[layers, slots, H/hp, N, hp*P]``, which it returns as its second
result; then come ``decay``, ``dt*x`` (``f32[rows, 1, H*P]`` each), ``B`` and
``C`` (``f32[rows, 1, G*N]`` each), and the first result is ``y``
``f32[rows, 1, H*P]``.

The algorithm has to read and write each row's state once, whatever the pool
holds: 2 x rows x (H*P*N) x 4 bytes, plus the five small rows. It needs about
6 operations a state element (the decay's multiply, the outer product's
multiply and its add, the multiply and the add of the product with C, and
the reduction's share): memory decides. Rows are the call's rows as traced,
padding included (a padding row does the same work on the scratch slot)."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 9:
        return None
    y, pool_out, li, slots, pool, decay, dtx, bm, cm = sh[:9]
    if (li != ("s32", [1]) or slots[0] != "s32" or len(slots[1]) != 1
            or pool[0] != "f32" or len(pool[1]) != 5 or pool_out != pool
            or decay != y or dtx != y or bm != cm or len(y[1]) != 3):
        return None
    rows = slots[1][0]
    if y[1][0] != rows or bm[1][0] != rows:
        return None
    state = pool[1][2] * pool[1][3] * pool[1][4]  # one row's state, one layer
    if state % y[1][2]:  # H*P*N over H*P
        return None
    small = sum(hlo.nbytes(s) for s in (y, decay, dtx, bm, cm))
    return {"flops": 6.0 * rows * state * call["count"],
            "bytes": (2.0 * rows * state * 4 + small) * call["count"],
            "peak": "bf16_flops_per_s"}
