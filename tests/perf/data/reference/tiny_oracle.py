"""A reference module that is not in ``perf/reference/``: the tests keep it
beside their data and a configuration names it (``"reference":
"tiny_oracle"``). Its equations are the repository's numpy oracle
(``tests/numpy_reference.py``, float64 on the host, no jax in the forward
pass), which shares no line with ``perf/reference/model.py``; its weights
are the engine's, by the common recipe.

Negative control: ``next_id`` (every log-probability moved to the next id).
"""

import numpy as np

from perf import config as configs
from perf.reference import weights as common
from tests import numpy_reference

VARIANTS = ("none", "next_id")


def weights(cfg):
    from production_stack_tpu.models import llama as prog

    return common.engine_params(
        prog.Llama(configs.program_model_config(cfg)), cfg.weights_seed,
        cfg.flag("--quantization"))


def teacher_force(cfg, params, sequences, variant):
    model_cfg = configs.program_model_config(cfg)
    tree = numpy_reference.dequant_tree(
        {k: ({n: np.asarray(w) for n, w in v.items()} if k == "layers"
             else np.asarray(v)) for k, v in params.items()})
    out = []
    for s in sequences:
        logits = numpy_reference.ref_decoder_forward(
            model_cfg, tree, s["tokens"]).astype(np.float64)
        logits -= logits.max(-1, keepdims=True)
        lps = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        lps = lps[s["n_prompt"] - 1: s["n_prompt"] - 1 + len(s["want"])]
        if variant == "next_id":
            lps = np.roll(lps, 1, axis=-1)
        out.append((lps.astype(np.float32), None))
    return out
