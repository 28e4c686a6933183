"""Operations and bytes of one call of the Mamba-1 decode kernel
(``ops/selective_scan.py`` ``selective_scan_decode``): every row's ``[N,
d_inner]`` state is read from the pool by its slot, one position of ``s <-
exp(dt A) s + (dt u) B^T``, ``y = s C + D u`` is applied, and the state is
written back in place. The call names the layer, the slots and which rows
start from zeros as scalar-prefetch operands (``s32[1]``, ``s32[rows]``,
``s32[rows]``) in front of the pool ``f32[layers, slots, N, d_inner]``, which
it returns as its second result; then come ``u`` and ``dt`` (``f32[rows, 1,
d_inner]`` each), ``A`` (``f32[N, d_inner]``), ``B`` and ``C`` (``f32[rows,
N, lanes]``: a column spread over a lane tile) and ``D``; the first result
is ``y`` ``f32[rows, 1, d_inner]``.

The algorithm has to read and write each row's state once, whatever the pool
holds: 2 x rows x N x d_inner x 4 bytes; ``A`` once; the row's ``u`` and
``dt`` in and ``y`` out; its ``B`` and ``C`` as the 2 x N numbers they are.
About 8 operations a state element (the decay's product, its exponential,
two products and an add for the update, a product and an add for ``C``, the
reduction's share): memory decides. Rows are the call's rows as traced,
padding included (a padding row does the same work on the scratch slot)."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    sh = hlo.shapes(call.get("text", ""))
    if len(sh) < 12:
        return None
    y, pool_out, li, slots, keep, pool, u, dt, a, bm, cm, d = sh[:12]
    if (li != ("s32", [1]) or slots[0] != "s32" or len(slots[1]) != 1
            or keep != slots or pool[0] != "f32" or len(pool[1]) != 4
            or pool_out != pool or u != y or dt != y or bm != cm
            or len(y[1]) != 3 or a[1] != pool[1][2:]):
        return None
    rows, n_state = slots[1][0], pool[1][2]
    if y[1][0] != rows or bm[1][:2] != [rows, n_state]:
        return None
    state = n_state * pool[1][3]  # one row's state, one layer
    small = (3 * hlo.nbytes(y) + hlo.nbytes(a) + hlo.nbytes(d)
             + 2 * rows * n_state * 4)
    return {"flops": 8.0 * rows * state * call["count"],
            "bytes": (2.0 * rows * state * 4 + small) * call["count"],
            "peak": "bf16_flops_per_s"}
