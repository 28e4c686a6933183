"""``perf/cost/paged_attn.py`` for a model whose layer stack runs
``total_ut_steps`` times a step over one set of weights, each pass on cache
slots of its own (pass ``t``, layer ``l``: slot ``t x num_hidden_layers +
l``): the keys and values a decode step has to read are those of
``num_hidden_layers x total_ut_steps`` layers, and the kernel is called once
for each. Everything else is that module's."""

from . import paged_attn


def cost(step: dict, hf: dict, cfg) -> dict:
    passes = int(hf.get("total_ut_steps") or 0)
    if passes < 1:
        return None
    return paged_attn.cost(
        step, dict(hf, num_hidden_layers=hf["num_hidden_layers"] * passes), cfg)
