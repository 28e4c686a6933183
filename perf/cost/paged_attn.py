"""Operations and bytes of the paged attention of one decode step
(``ops/paged_attention_pallas.py``, every layer of it), from the step's own
metadata: the trace names the kernel but its bytes depend on how long each
row's context is, which only the program knows (``pst.step_info``).

The algorithm has to read every row's keys and values once in every layer:
``kv_tokens`` (the sum of the rows' context lengths after the step's
tokens) x 2 x KV heads x head size x bytes of the KV dtype x layers; and the
queries in and the result out, rows x heads x head size x 2 bytes each.
Operations: q.k and p.v, 2 x 2 x heads x head size per row and context
token. A burst of n tokens a row (``new_tokens`` / ``rows``) reads contexts
that grow by one a token, ending at ``kv_tokens``. The kernel reads whole
pages and all of a page's heads; that is what the share measures."""

KV_BYTES = {"float8_e4m3fn": 1, "float8_e5m2": 1, "fp8": 1, "int8": 1}


def cost(step: dict, hf: dict, cfg) -> dict:
    rows, kv_tokens = step.get("rows"), step.get("kv_tokens")
    if not rows or not kv_tokens:
        return None
    n = max(int(step.get("new_tokens") or rows) // rows, 1)
    context = n * kv_tokens - rows * n * (n - 1) / 2  # summed over the burst
    heads, layers = hf["num_attention_heads"], hf["num_hidden_layers"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    kv_bytes = KV_BYTES.get(cfg.flag("--kv-cache-dtype") if cfg else None, 2)
    return {
        "flops": 4.0 * heads * head_dim * context * layers,
        "bytes": (context * 2 * hf["num_key_value_heads"] * head_dim * kv_bytes
                  + n * rows * heads * head_dim * 2 * 2) * layers,
        "peak": "bf16_flops_per_s",
    }
