"""Operations and bytes of one whole verify-and-draft step of the
``exaone_moe`` class (the program ``jit_pst_decode_step_mtp`` as a whole, not
one kernel of it), from the step's own metadata (``pst.step_info`` with
``step`` = ``mtp_verify``): ``rows`` rows of two positions each through the
main stack, the head, the draft module and the head again.

What the algorithm has to move a step:

- every layer's attention weights once (q, k, v, o and the norms), the dense
  layers' blocks, of each expert layer (the draft module's among them) the
  router, the shared expert and **the held experts that got a pair**: the
  reader hands a cost function no counter, so their number is the
  expectation under even routing, ``held x (1 - (1 - k / all) ^ tokens)``
  (all 16 at 64 rows; ``moe.mtp_top8_experts_touched_share`` says what the
  router did); the draft module's projection; the head twice (the draft
  module needs the main head's token before its own logits: 236 MB does not
  stay on the chip between them);
- the pages, as ``perf/cost/paged_attn_verify.py`` counts them;
- the tokens' activations through every layer: the residual stream in and
  out of both sub-blocks, q, k, v, the attention's result, and the block's
  gate, up and product (a token's 8 pairs at the expert width), 2 B each.

Operations: 2 x the matrix parameters a token meets x tokens, the head's
twice, and the attention's. The least time is the larger of operations over
the bf16 peak and bytes over the bandwidth
(``perf/readers/trace_step_module_roofline.py``); at 64 rows the bytes
decide."""

from . import paged_attn_verify


def cost(step: dict, hf: dict, cfg) -> dict:
    pages = paged_attn_verify.cost(step, hf, cfg)
    if pages is None:
        return None
    tokens = step["rows"] * paged_attn_verify.POSITIONS
    kinds = list(hf.get("mlp_layer_types") or [])
    n_mtp = int(hf.get("num_nextn_predict_layers", 0))
    layers = hf["num_hidden_layers"] + n_mtp
    dense = kinds.count("dense")
    sparse = len(kinds) - dense + n_mtp
    heads, d = hf["num_attention_heads"], hf["hidden_size"]
    head_dim = hf.get("head_dim") or d // heads
    q, kv = heads * head_dim, hf["num_key_value_heads"] * head_dim
    f, fe = hf["intermediate_size"], hf["moe_intermediate_size"]
    k = hf["num_experts_per_tok"]
    held = hf["num_experts"]
    scored = int((hf.get("ep_share") or {}).get("of", held))
    attn = 2 * d * q + 2 * d * kv
    expert = 3 * d * fe
    shared = expert * int(hf.get("num_shared_experts", 0))
    touched = held * (1.0 - (1.0 - k / scored) ** tokens)
    head = hf["vocab_size"] * d
    weights = (attn * layers + 3 * d * f * dense
               + (d * scored + shared + expert * touched) * sparse
               + 2 * d * d * n_mtp + head * (1 + n_mtp))
    # what one token multiplies through: its share of the held experts is
    # k x held / scored pairs a layer
    met = (attn * layers + 3 * d * f * dense
           + (d * scored + shared + expert * k * held / scored) * sparse
           + 2 * d * d * n_mtp + head * (1 + n_mtp))
    activations = 2.0 * (
        (4 * d + 2 * q + 2 * kv) * layers + 3 * f * dense
        + 3 * fe * (k * held / scored + 1) * sparse)
    return {
        "flops": 2.0 * met * tokens + pages["flops"],
        "bytes": weights * 2.0 + activations * tokens + pages["bytes"],
        "peak": "bf16_flops_per_s",
    }
