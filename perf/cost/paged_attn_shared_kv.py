"""Operations and bytes of the paged attention of one decode step of a
decoder-hybrid-decoder (``models/phi4flash.py``): ``n / 4`` window layers
each read a row's last ``sliding_window`` tokens from their own pages, and
the one full-attention layer's pages are read ``n / 4`` times, once by
itself and once by each cross-attention layer. From the step's own metadata
(``pst.step_info``): ``kv_tokens`` sums the rows' context lengths,
``window_tokens`` what the window layers read of them (a row at most its
window).

A token's keys and values are ``2 x num_key_value_heads x head`` numbers a
layer (5,120 bytes at the published widths in bf16). Operations: the two
score products of a pair of query heads over ``head`` numbers each and the
four ``p v`` products over ``head`` each: ``2 x heads x head x 3`` a context
token and layer. Absent where the step carries no ``window_tokens`` (a
program without the window group)."""


def cost(step: dict, hf: dict, cfg) -> dict:
    rows, kv_tokens = step.get("rows"), step.get("kv_tokens")
    window_tokens = step.get("window_tokens")
    if not rows or not kv_tokens or window_tokens is None:
        return None
    if hf.get("model_type") != "phi4flash":
        return None
    heads, layers = hf["num_attention_heads"], hf["num_hidden_layers"] // 4
    head = hf["hidden_size"] // heads
    token = 2 * hf["num_key_value_heads"] * head * 2  # bf16 pages
    context = (kv_tokens + window_tokens) * layers
    return {
        "flops": 6.0 * heads * head * context,
        "bytes": context * token + 2 * layers * rows * heads * 2 * head * 2 * 2,
        "peak": "bf16_flops_per_s",
    }
