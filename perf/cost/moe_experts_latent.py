"""Operations and bytes of one grouped product of the latent expert layer
(``models/nemotron_h.py::routed_latent``: JAX's ``megablox.gmm`` kernel,
``%gmm`` in the trace): ``out[rows, N] = lhs[rows, K] @ bank[expert of the
row]`` over pair rows sorted by expert, ``bank`` ``bf16[experts, K, N]``.

The kernel reads the weights of the experts that got a row, not the bank: at
decode widths a step's 176 held pairs fall on a little over half of 128 held
experts. Neither the call's shapes nor ``pst.step_info`` say how many; the
program counts them (``pst:moe_experts_touched_total`` over
``pst:moe_layer_steps_total``) and the reader hands the window's means on
under ``counted``: ``experts_touched`` and ``pairs_held``, each a layer and
step. The algorithm has to read each touched expert's ``K x N`` matrix once,
each real pair's row in and its result out, and needs 2 x pairs x K x N
operations: memory decides. Rows as traced are padded to the kernel's row
tile and to a prefill step's budget; padding is not work. A prefill step's
calls touch every expert and carry more pairs than the window's mean, so
they are costed low: the share errs low where prefill weighs."""

from . import hlo


def cost(call: dict, hf: dict, cfg) -> dict:
    counted = call.get("counted") or {}
    touched, pairs = counted.get("experts_touched"), counted.get("pairs_held")
    if touched is None or pairs is None:
        return None
    sh = hlo.shapes(call.get("text", ""))
    if not sh or len(sh[0][1]) != 2:
        return None
    out = sh[0]
    lhs = next((s for s in sh[1:] if s[0] == "bf16" and len(s[1]) == 2), None)
    bank = next((s for s in sh[1:] if len(s[1]) == 3), None)
    if lhs is None or bank is None:
        return None
    rows, n = out[1]
    experts, k, bank_n = bank[1]
    if lhs[1] != [rows, k] or bank_n != n:
        return None
    touched, pairs = min(touched, experts), min(pairs, rows)
    per_weight = hlo.nbytes((bank[0], [1]))
    return {"flops": 2.0 * pairs * k * n * call["count"],
            "bytes": (touched * k * n * per_weight
                      + pairs * (k * per_weight + n * hlo.nbytes((out[0], [1]))))
            * call["count"],
            "peak": "bf16_flops_per_s"}
