"""``perf/cost/paged_attn.py`` for a model whose every
``full_attention_interval``-th layer is attention and whose others keep no
pages: the keys and values a decode step has to read are those of
``num_hidden_layers // full_attention_interval`` layers. Everything else
(``head_dim`` as published, here 256; the KV dtype) is that module's."""

from . import paged_attn


def cost(step: dict, hf: dict, cfg) -> dict:
    interval = hf.get("full_attention_interval")
    if not interval or hf["num_hidden_layers"] < interval:
        return None
    return paged_attn.cost(
        step, dict(hf, num_hidden_layers=hf["num_hidden_layers"] // interval),
        cfg)
