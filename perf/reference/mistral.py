"""The Mistral / Mixtral reference as ``run.py`` asks for one (the
interface is in ``perf/reference/__init__.py``): the equations of
``model.py`` over the weights the engine serves, one layer's weights at a
time, every sequence through a layer before the next layer is widened.

Negative controls: ``no_renorm`` (top-k router weights not renormalised),
``rope_1e4`` (rotary base 10,000 instead of the configuration's).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import model as ref
from perf.reference import weights as common

VARIANTS = ref.VARIANTS
LAYER_MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def weights(cfg):
    from production_stack_tpu.models import llama as prog

    return common.engine_params(
        prog.Llama(configs.program_model_config(cfg)), cfg.weights_seed,
        cfg.flag("--quantization"))


def layer_weights(params, li: int):
    """Layer ``li``'s weights for :func:`perf.reference.model.layer`, in
    float32. A MoE layer's unquantised expert banks are the exception: a
    Mixtral layer's experts are 5.6 GB in float32 and even one layer's
    slice of the stored bank is a copy the chip has no room for beside the
    tree, so the whole stacked bank is handed on untouched with ``li``
    beside it, and the layer widens one expert at a time."""
    layers = params["layers"]
    moe = "w_router" in layers
    out = {}
    for name, leaf in layers.items():
        if name.endswith(("_qs", "_q4s")) or name.startswith("lora_"):
            continue
        if name in LAYER_MATMULS:
            q4s = layers.get(name + "_q4s")
            qs = layers.get(name + "_qs")
            if moe and name in ("w_gate", "w_up", "w_down") and q4s is None and qs is None:
                out[name] = leaf
                out["li"] = jnp.int32(li)
            else:
                out[name] = common.matmul_leaf(
                    leaf[li], None if q4s is None else q4s[li],
                    None if qs is None else qs[li])
        else:
            out[name] = leaf[li].astype(jnp.float32)
    return out


def teacher_force(cfg, params, sequences, variant: str) -> list:
    hf = cfg.hf
    n_layers = hf["num_hidden_layers"]
    n_heads = hf["num_attention_heads"]
    n_kv = hf.get("num_key_value_heads", n_heads)
    head_dim = hf.get("head_dim") or hf["hidden_size"] // n_heads
    eps = float(hf.get("rms_norm_eps", 1e-5))
    top_k = int(hf.get("num_experts_per_tok", 2))
    theta = 1e4 if variant == "rope_1e4" else float(hf["rope_theta"])
    xs, tabs, gaps = [], [], []
    for s in sequences:
        padded = ref.pad_len(len(s["tokens"]))
        ids = np.zeros(padded, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        xs.append(common.embed_rows(params, jnp.asarray(ids)))
        cos, sin = ref.rope_tables(padded, head_dim, theta)
        tabs.append((jnp.asarray(cos), jnp.asarray(sin)))
        gaps.append(np.full(padded, np.inf, np.float32))
    for li in range(n_layers):
        lw = layer_weights(params, li)
        for i in range(len(sequences)):
            xs[i], gap = ref.layer(
                xs[i], tabs[i][0], tabs[i][1], lw, n_heads=n_heads,
                n_kv=n_kv, top_k=top_k, eps=eps,
                renorm=variant != "no_renorm")
            gaps[i] = np.minimum(gaps[i], np.asarray(gap))
        del lw
    final_norm, lm_head = common.head_weights(params)
    moe = "w_router" in params["layers"]
    out = []
    for i, s in enumerate(sequences):
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(ref.head_logprobs(
            xs[i][rows], final_norm, lm_head, eps=eps))
        out.append((lps, gaps[i][n_prompt - 1: n_prompt - 1 + n_gen]
                    if moe else None))
    return out
