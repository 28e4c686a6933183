"""``perf/cost/paged_attn.py`` for a model whose layers are not all
attention: the keys and values a decode step has to read are those of the
attention blocks alone (the ``*`` of ``hybrid_override_pattern``), not of
``num_hidden_layers`` layers. Everything else is that module's."""

from . import paged_attn


def cost(step: dict, hf: dict, cfg) -> dict:
    pattern = hf.get("hybrid_override_pattern")
    if not pattern or "*" not in pattern:
        return None
    return paged_attn.cost(
        step, dict(hf, num_hidden_layers=pattern.count("*")), cfg)
