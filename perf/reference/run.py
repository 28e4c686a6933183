#!/usr/bin/env python3
"""Child 2 of a benchmark run: takes the chip after the engine has gone and
computes the plain reference for the check set.

    python perf/reference/run.py <request.json> <result.json>

``request.json``: ``{"config_file", "variants": [...], "sequences":
[{"id", "tokens": [...], "n_prompt", "want": [[ids reported at generated
position 0], ...]}], "reference_dirs": [...]}`` (the last optional: where
to look for the module before ``perf/reference/``). Every sequence is
teacher-forced on the system's own tokens (prompt + what it generated); for
each generated position the result holds the reference's log-probability of
every id in ``want``, its own arg-max, and the smallest top-k/next
router-logit gap over the layers (``inf`` for a model without a router).

What is common to every architecture is here: the request, the compile
cache, the configuration, the loop over variants, the result's shape. The
equations are the module's that the configuration file names under
``reference`` (``perf/reference/__init__.py`` has the interface).
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

    from perf import config as configs
    from perf import reference

    t0 = time.monotonic()
    cfg = configs.load(request["config_file"])
    module = reference.load(cfg.reference, request.get("reference_dirs"))
    variants = request.get("variants") or ["none"]
    unknown = [v for v in variants if v not in module.VARIANTS]
    if unknown:
        raise ValueError(f"unknown variant {unknown}: {module.__file__} has "
                         f"{list(module.VARIANTS)}")
    params = module.weights(cfg)
    jax.block_until_ready(params)
    log(f"[reference] {cfg.reference}: weights ready "
        f"+{time.monotonic() - t0:.1f}s on {jax.devices()[0].platform}")
    seqs = request["sequences"]
    out = {}
    for variant in variants:
        results = []
        for s, (lps, gap) in zip(
                seqs, module.teacher_force(cfg, params, seqs, variant)):
            n_gen = len(s["want"])
            results.append({
                "id": s["id"],
                "logprobs": [
                    {str(t): float(lps[p, t]) for t in s["want"][p]}
                    for p in range(n_gen)
                ],
                "argmax": [int(a) for a in lps.argmax(-1)],
                "gap": [float("inf")] * n_gen if gap is None
                       else [float(g) for g in gap],
            })
        out[variant] = results
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
