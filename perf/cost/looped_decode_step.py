"""Operations and bytes of one whole decode step of a looped dense stack
(``total_ut_steps`` passes over ``num_hidden_layers`` layers of weights: the
program ``jit_pst_decode_step*`` as a whole, not one kernel of it), from the
step's own metadata (``pst.step_info``).

What the algorithm has to move, a token of the burst (``new_tokens`` /
``rows`` of them):

- the layers' weights once a **pass** (nothing keeps 5 GB of them on the
  chip between passes): ``total_ut_steps`` x layers x (q, k, v, o, gate, up,
  down and the four norms) at the stored width (2 B; 1 B under
  ``--quantization int8``, half a byte and a float32 scale a group of 128
  under ``int4``), and the head once (``vocab x hidden`` at 2 B; the
  embedding's ``rows`` rows are left out);
- the pages, as ``perf/cost/paged_attn_looped.py`` counts them: every row's
  keys and values in each of ``layers x passes`` slots, queries in and
  results out;
- the rows' activations through every layer of every pass: the residual
  stream in and out of both sub-blocks, q, k, v, the attention's result, and
  the MLP's gate, up and product, 2 B each.

Operations: 2 x the layers' matrix parameters x passes x rows, 2 x vocab x
hidden x rows for the head, and the attention's. The least time is the
larger of operations over the bf16 peak and bytes over the bandwidth
(``perf/readers/trace_step_module_roofline.py``); at 16 rows the bytes
decide by a factor of 27."""

from . import paged_attn_looped

WEIGHT_BYTES = {None: 2.0, "int8": 1.0, "int4": 0.5 + 4.0 / 128}


def cost(step: dict, hf: dict, cfg) -> dict:
    pages = paged_attn_looped.cost(step, hf, cfg)
    if pages is None:
        return None
    rows = step["rows"]
    tokens = max(int(step.get("new_tokens") or rows), rows)  # rows x depth
    depth = tokens // rows
    passes, layers = int(hf["total_ut_steps"]), hf["num_hidden_layers"]
    heads, d, f = hf["num_attention_heads"], hf["hidden_size"], hf["intermediate_size"]
    head_dim = hf.get("head_dim") or d // heads
    q, kv = heads * head_dim, hf["num_key_value_heads"] * head_dim
    matrices = 2 * d * q + 2 * d * kv + 3 * d * f  # one layer's, in weights
    width = WEIGHT_BYTES[cfg.flag("--quantization") if cfg else None]
    layer_bytes = matrices * width + 4 * d * 2
    head = hf["vocab_size"] * d
    activations = 2.0 * (4 * d + 2 * q + 2 * kv + 3 * f)  # a token and layer
    return {
        "flops": (2.0 * matrices * layers * passes + 2.0 * head) * tokens
                 + pages["flops"],
        "bytes": ((layer_bytes * layers * passes + head * 2.0) * depth
                  + activations * layers * passes * tokens + pages["bytes"]),
        "peak": "bf16_flops_per_s",
    }
