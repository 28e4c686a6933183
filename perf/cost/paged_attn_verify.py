"""Operations and bytes of the paged attention of one verify-and-draft step
(``--speculative-mtp``: two query positions a row, the last committed token
and its draft, through every attention layer and then the draft module's),
from the step's own metadata (``pst.step_info`` with ``step`` =
``mtp_verify``): ``kv_tokens`` sums the rows' context lengths after the
step's two positions, ``window_tokens`` what a window layer reads of them.

The algorithm has to read ``kv_tokens`` keys and values once in every layer
of the global page group (the ``full_attention`` layers and the
``num_nextn_predict_layers`` draft layers, which attend over every earlier
position) and ``window_tokens`` once in every ``sliding_attention`` layer,
``2 x num_key_value_heads x head x 2`` bytes a token and layer (4,096 at the
published widths in bf16), **once for both positions**, and the queries in
and the results out, rows x 2 positions x heads x head x 2 bytes each, in
every layer. Operations: q.k and p.v for both positions, ``2 x 2 x 2 x heads
x head`` a row and token read. The kernel (the chunk kernel at a tile of two
query rows) reads whole pages; that is what the share measures. Absent for
any other step."""

SLIDING, FULL = "sliding_attention", "full_attention"
POSITIONS = 2


def cost(step: dict, hf: dict, cfg) -> dict:
    rows, kv_tokens = step.get("rows"), step.get("kv_tokens")
    window_tokens = step.get("window_tokens")
    types = hf.get("layer_types")
    if (step.get("step") != "mtp_verify" or not rows or not kv_tokens
            or window_tokens is None or not types):
        return None
    whole = types.count(FULL) + int(hf.get("num_nextn_predict_layers", 0))
    sliding = types.count(SLIDING)
    heads = hf["num_attention_heads"]
    head = hf.get("head_dim") or hf["hidden_size"] // heads
    token = 2 * hf["num_key_value_heads"] * head * 2  # bf16 pages
    context = kv_tokens * whole + window_tokens * sliding
    return {
        "flops": 4.0 * heads * head * context * POSITIONS,
        "bytes": (context * token
                  + (whole + sliding) * rows * POSITIONS * heads * head * 2 * 2),
        "peak": "bf16_flops_per_s",
    }
