#!/usr/bin/env python3
"""Child 2 of a benchmark run: takes the chip after the engine has gone and
computes the plain reference for the check set.

    python perf/reference/run.py <request.json> <result.json>

``request.json``: ``{"config_file", "variants": [...], "sequences":
[{"id", "tokens": [...], "n_prompt", "want": [[ids reported at generated
position 0], ...]}]}``. Every sequence is teacher-forced on the system's
own tokens (prompt + what it generated); for each generated position the
result holds the reference's log-probability of every id in ``want``, its
own arg-max, and the smallest top-k/next router-logit gap over the layers
(``inf`` for a dense model).
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _place_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.default_backend() != "tpu":
            return
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compute(request: dict, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf import config as configs
    from perf.reference import model as ref
    from perf.reference import weights

    t0 = time.monotonic()
    cfg = configs.load(request["config_file"])
    hf = cfg.hf
    model_cfg = configs.program_model_config(cfg)
    params = weights.engine_params(
        model_cfg, cfg.weights_seed, cfg.flag("--quantization"))
    jax.block_until_ready(params)
    log(f"[reference] weights ready +{time.monotonic() - t0:.1f}s on "
        f"{jax.devices()[0].platform}")
    n_layers = hf["num_hidden_layers"]
    n_heads = hf["num_attention_heads"]
    n_kv = hf.get("num_key_value_heads", n_heads)
    head_dim = hf.get("head_dim") or hf["hidden_size"] // n_heads
    eps = float(hf.get("rms_norm_eps", 1e-5))
    top_k = int(hf.get("num_experts_per_tok", 2))
    seqs = request["sequences"]
    out = {}
    for variant in request.get("variants") or ["none"]:
        if variant not in ref.VARIANTS:
            raise ValueError(f"unknown variant {variant!r}")
        theta = 1e4 if variant == "rope_1e4" else float(hf["rope_theta"])
        xs, tabs, gaps = [], [], []
        for s in seqs:
            padded = ref.pad_len(len(s["tokens"]))
            ids = np.zeros(padded, np.int32)
            ids[: len(s["tokens"])] = s["tokens"]
            xs.append(weights.embed_rows(params, jnp.asarray(ids)))
            cos, sin = ref.rope_tables(padded, head_dim, theta)
            tabs.append((jnp.asarray(cos), jnp.asarray(sin)))
            gaps.append(np.full(padded, np.inf, np.float32))
        for li in range(n_layers):
            lw = weights.layer_weights(params, li)
            for i in range(len(seqs)):
                xs[i], gap = ref.layer(
                    xs[i], tabs[i][0], tabs[i][1], lw, n_heads=n_heads,
                    n_kv=n_kv, top_k=top_k, eps=eps,
                    renorm=variant != "no_renorm")
                gaps[i] = np.minimum(gaps[i], np.asarray(gap))
            del lw
        final_norm, lm_head = weights.head_weights(params)
        results = []
        for i, s in enumerate(seqs):
            n_prompt, n_gen = s["n_prompt"], len(s["want"])
            rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
            lps = np.asarray(ref.head_logprobs(
                xs[i][rows], final_norm, lm_head, eps=eps))
            results.append({
                "id": s["id"],
                "logprobs": [
                    {str(t): float(lps[p, t]) for t in s["want"][p]}
                    for p in range(n_gen)
                ],
                "argmax": [int(a) for a in lps.argmax(-1)],
                "gap": [float(g) for g in
                        gaps[i][n_prompt - 1: n_prompt - 1 + n_gen]],
            })
        out[variant] = results
        del lm_head
        log(f"[reference] variant {variant}: {len(seqs)} sequences, "
            f"{sum(len(s['tokens']) for s in seqs)} tokens "
            f"+{time.monotonic() - t0:.1f}s")
    dev = jax.devices()[0]
    return {"variants": out, "seconds": time.monotonic() - t0,
            "platform": dev.platform, "device_kind": dev.device_kind}


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    _place_compile_cache()
    with open(argv[1]) as f:
        request = json.load(f)
    result = compute(request)
    tmp = argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
