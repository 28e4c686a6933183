"""What an expert layer's dispatch and way back cost on the chip, beside its
grouped products.

    chiprun -- python scripts/tpu_moe_combine_attrib.py [--tag x] [--shapes qwen_t1024 ...]

One expert layer as the three expert cells' models call it
(``moe_dispatch.routed_experts``: router, sort by expert, ``x[tok]``, the
body's two ``megablox.gmm`` products, the weighted sum back at the tokens),
at each cell's published widths and at the step shapes its programs have: a
prefill step at its token budget, a packed one padded to two, four or eight
times the budget (``b8xt1024``: five prompts, one past 512), a decode step. A scan over ``LAYERS`` such layers is traced with
the JAX profiler and the device's ``XLA Ops`` are summed by name: the
``%gmm`` kernels' own times, and every other operation of the loop body,
which is the dispatch's bookkeeping (PERF.md §6, PR 42). Times are per layer,
in microseconds, the mean over every layer of every traced repeat.

The script imports nothing of the program but ``routed_experts`` (and the
benchmark's ``perf/trace.py`` to read the capture), so a copy of it runs on a
tree whose dispatch goes back another way: copy it into that tree's
``scripts/`` and compare the two reports
(``chiprun_out/moe_combine_attrib/<tag>.json``).
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perf import trace  # noqa: E402
from production_stack_tpu.models.moe_dispatch import routed_experts  # noqa: E402

LAYERS = 4
REPEATS = 8
OUT_DIR = os.path.join("chiprun_out", "moe_combine_attrib")

# The expert layer of each cell's configuration (perf/configs/*.json):
# router input width, experts' input width (the latent where it differs),
# inner width, gated or not, experts the router scores, held here, top k,
# the router's scoring and scale, layers' banks seen as one.
MODELS = {
    "qwen": dict(d=2048, k_in=2048, inner=512, gated=True, scored=512,
                 held=64, top_k=10, scoring="softmax", scale=1.0, bank=2),
    "nemotron": dict(d=4096, k_in=1024, inner=2688, gated=False, scored=512,
                     held=128, top_k=22, scoring="sigmoid", scale=5.0,
                     bank=None),
    "glm": dict(d=2048, k_in=2048, inner=1536, gated=True, scored=64,
                held=64, top_k=4, scoring="sigmoid", scale=1.8, bank=2),
}
# name -> model, tokens of the step as padded, its token budget, real tokens
SHAPES = {
    "qwen_t1024": ("qwen", 1024, 1024, 700),
    "qwen_t2048": ("qwen", 2048, 1024, 700),
    "qwen_t8192": ("qwen", 8192, 1024, 700),
    "qwen_b64": ("qwen", 64, None, 64),
    "nemotron_t1024": ("nemotron", 1024, 1024, 700),
    "nemotron_t8192": ("nemotron", 8192, 1024, 700),
    "nemotron_b32": ("nemotron", 32, None, 32),
    "glm_t1024": ("glm", 1024, 1024, 700),
    "glm_t4096": ("glm", 4096, 1024, 700),
    "glm_b16": ("glm", 16, None, 16),
}


def _weights(m):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    groups = m["held"] * (m["bank"] or 1)
    up = m["inner"] * (2 if m["gated"] else 1)
    bf = lambda k, s: (0.02 * jax.random.normal(k, s, jnp.float32)
                       ).astype(jnp.bfloat16)
    return dict(
        w_router=jax.random.normal(keys[0], (m["d"], m["scored"]), jnp.float32),
        bias=0.01 * jax.random.normal(keys[1], (m["scored"],), jnp.float32),
        w1=bf(keys[2], (groups, m["k_in"], up)),
        w2=bf(keys[3], (groups, m["inner"], m["k_in"])),
    )


def layers_fn(m, budget, u, valid, w):
    """``LAYERS`` expert layers in a scan, each fed by the one before."""
    inner = m["inner"]

    def body_of(xs, gmm):
        a = gmm(xs, w["w1"])
        if m["gated"]:
            a = jax.nn.silu(a[:, :inner]) * a[:, inner:]
        else:
            a = jnp.square(jax.nn.relu(a))
        return gmm(a.astype(jnp.bfloat16), w["w2"])

    def layer(u, li):
        x = u[:, :m["k_in"]]
        kw = {}
        if m["bank"]:
            kw = dict(bank_experts=w["w1"].shape[0],
                      bank_first=(li % m["bank"]) * m["held"])
        y, stats = routed_experts(
            u, x, valid, w["w_router"],
            w["bias"] if m["scoring"] == "sigmoid" else None, body_of,
            top_k=m["top_k"], norm_topk_prob=True, scale=m["scale"],
            scoring=m["scoring"], held=m["held"], expert_first=0,
            token_budget=budget, **kw)
        y = jnp.pad(y, ((0, 0), (0, m["d"] - m["k_in"])))
        h = u.astype(jnp.float32) + y
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
        return h.astype(jnp.bfloat16), stats

    return jax.lax.scan(layer, u, jnp.arange(LAYERS, dtype=jnp.int32))


def device_ops(trace_dir):
    """Self seconds by operation (``%name shape``) on the device's ``XLA
    Ops`` line, as the benchmark reads a trace."""
    path = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    reduced = trace.reduce(trace.extract(path))
    if not reduced["device_planes"]:
        raise SystemExit(f"no device plane in {path}")
    return reduced["ops"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--tag", default="change")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("tpu_moe_combine_attrib: no chip; times of the interpreted "
              "kernel say nothing", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    report = {"device": dev.device_kind, "layers": LAYERS, "shapes": {}}
    weights = {}
    for name in args.shapes:
        model, n, budget, real = SHAPES[name]
        m = MODELS[model]
        if model not in weights:
            weights.clear()  # one model's banks on the device at a time
            weights[model] = _weights(m)
        w = weights[model]
        u = jax.random.normal(
            jax.random.PRNGKey(n), (n, m["d"]), jnp.bfloat16)
        valid = (jnp.arange(n) * real) % n < real  # real of n, spread
        fn = jax.jit(lambda u, valid, w, m=m, budget=budget:
                     layers_fn(m, budget, u, valid, w))
        out, stats = jax.block_until_ready(fn(u, valid, w))
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(u, valid, w)
        jax.block_until_ready(out)
        wall_us = (time.perf_counter() - t0) / REPEATS / LAYERS * 1e6
        trace_dir = os.path.join(OUT_DIR, f"trace_{args.tag}_{name}")
        with jax.profiler.trace(trace_dir):
            for _ in range(REPEATS):
                out = fn(u, valid, w)
            jax.block_until_ready(out)
        per_layer = {o: s / REPEATS / LAYERS * 1e6
                     for o, s in device_ops(trace_dir).items()}
        shutil.rmtree(trace_dir)
        kernels = {o: t for o, t in per_layer.items() if o.startswith("%gmm")}
        others = {o: t for o, t in per_layer.items() if o not in kernels}
        report["shapes"][name] = {
            "wall_us_a_layer": round(wall_us, 2),
            "gmm_us_a_layer": round(sum(kernels.values()), 2),
            "other_ops_us_a_layer": round(sum(others.values()), 2),
            "held_pairs_a_layer": float(stats[0, 1]),
            "finite": bool(jnp.isfinite(out[0].astype(jnp.float32)).all()),
            "other_ops": {o: round(t, 2) for o, t in sorted(
                others.items(), key=lambda kv: -kv[1])[:24]},
        }
        print(json.dumps({name: report["shapes"][name]}), flush=True)
    with open(os.path.join(OUT_DIR, f"{args.tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
