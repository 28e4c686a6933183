"""Operations and bytes of the paged attention of one decode step of a model
whose ``layer_types`` mix ``full_attention`` layers, which read a row's whole
context from the global page group, and ``sliding_attention`` layers, which
read at most a row's last ``sliding_window`` tokens from a page group of
their own. From the step's own metadata (``pst.step_info``): ``kv_tokens``
sums the rows' context lengths after the step's tokens, ``window_tokens``
what a window layer reads of them (a row at most its window).

The algorithm has to read ``kv_tokens`` keys and values once in every
``full_attention`` layer and ``window_tokens`` once in every
``sliding_attention`` layer, ``2 x num_key_value_heads x head x 2`` bytes a
token and layer (2,048 at the published widths in bf16), and the queries in
and the result out, rows x heads x head x 2 bytes each, in every layer.
Operations: q.k and p.v, ``2 x 2 x heads x head`` a row and token read. The
kernel reads whole pages and all of a page's heads; that is what the share
measures. Absent where the step carries no ``window_tokens`` (a program
without the window group) or the configuration no ``layer_types``."""

SLIDING, FULL = "sliding_attention", "full_attention"


def cost(step: dict, hf: dict, cfg) -> dict:
    rows, kv_tokens = step.get("rows"), step.get("kv_tokens")
    window_tokens = step.get("window_tokens")
    types = hf.get("layer_types")
    if not rows or not kv_tokens or window_tokens is None or not types:
        return None
    if int(step.get("new_tokens") or rows) // rows > 1:
        return None  # a burst of several tokens a row: not what this counts
    # (a chained step counts the rows still alive: fewer than its rows)
    full, sliding = types.count(FULL), types.count(SLIDING)
    heads = hf["num_attention_heads"]
    head = hf.get("head_dim") or hf["hidden_size"] // heads
    token = 2 * hf["num_key_value_heads"] * head * 2  # bf16 pages
    context = kv_tokens * full + window_tokens * sliding
    return {
        "flops": 4.0 * heads * head * context,
        "bytes": (context * token
                  + (full + sliding) * rows * heads * head * 2 * 2),
        "peak": "bf16_flops_per_s",
    }
