"""Operations and bytes of the latent attention of one decode step
(``ops/mla_attention.py::mla_decode``, every layer of it), from the step's
own metadata (``pst.step_info``): the trace names the kernel, how long each
row's context is only the program knows.

A token's cache row in a layer is ``[c_kv | k_rope]``: ``kv_lora_rank +
qk_rope_head_dim`` elements, read **once** (keys are the whole row, values
its first ``kv_lora_rank`` elements). The algorithm has to read
``kv_tokens`` such rows in every layer as stored (two bytes an element: the
configuration keeps bf16 latents; the lanes a row is padded to on the chip
are the layout's cost, not the algorithm's, and are not counted), the
absorbed queries in (``rows x heads x (rank + rope)``) and the weighted
latents out (``rows x heads x rank``). Operations: scores against the whole
row and the weighted sum over its latent part, ``2 x heads x (2 x rank +
rope)`` a row and context token; at 20 heads that is 38 operations a byte
against the chip's ridge of 240, so memory decides unless the kernel's
geometry wastes the MXU. A burst of n tokens a row reads contexts that grow
by one a token, ending at ``kv_tokens``."""


def cost(step: dict, hf: dict, cfg) -> dict:
    rows, kv_tokens = step.get("rows"), step.get("kv_tokens")
    rank, rope = hf.get("kv_lora_rank"), hf.get("qk_rope_head_dim")
    if not rows or not kv_tokens or not rank or rope is None:
        return None
    n = max(int(step.get("new_tokens") or rows) // rows, 1)
    context = n * kv_tokens - rows * n * (n - 1) / 2  # summed over the burst
    heads, layers = hf["num_attention_heads"], hf["num_hidden_layers"]
    return {
        "flops": 2.0 * heads * (2 * rank + rope) * context * layers,
        "bytes": (context * (rank + rope) * 2
                  + n * rows * heads * (2 * rank + rope) * 2) * layers,
        "peak": "bf16_flops_per_s",
    }
