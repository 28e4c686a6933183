"""What an expert layer's dispatch and way back cost on the chip, beside its
grouped products.

    chiprun -- python scripts/tpu_moe_combine_attrib.py [--tag x] [--shapes qwen_t1024 ...]

One expert layer as the four expert cells' models call it
(``moe_dispatch.routed_experts``: router, sort by expert, ``x[tok]``, the
body's two ``megablox.gmm`` products, the weighted sum back at the tokens),
at each cell's published widths and at the step shapes its programs have: a
prefill step at its token budget, a packed one padded to two, four or eight
times the budget (``b8xt1024``: five prompts, one past 512), a decode step. A scan over ``LAYERS`` such layers is traced with
the JAX profiler and the device's ``XLA Ops`` are summed by name: the
``%gmm`` kernels' own times, and every other operation of the loop body,
which is the dispatch's bookkeeping (PERF.md §6, PR 42). Times are per layer,
in microseconds, the mean over every layer of every traced repeat.

Since PR 52 the bookkeeping is also summed by **item**, under names that
do not depend on how a tree computes them (``ITEMS``): each traced
operation is looked up in the compiled program's text and put by the scope
it was traced under (``moe_router``, ``moe_experts``, this script's own
``attrib_glue``), by what its root does and by its result's shape. An
operation XLA fused across two items counts under its root's.

The script imports nothing of the program but ``routed_experts`` (and the
benchmark's ``perf/trace.py`` to read the capture), so a copy of it runs on a
tree whose dispatch goes another way: copy it into that tree's
``scripts/`` and compare the two reports
(``chiprun_out/moe_combine_attrib/<tag>.json``).
"""

import argparse
import glob
import json
import os
import re
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
    "mellum": dict(d=2304, k_in=2304, inner=896, gated=True, scored=64,
                   held=16, top_k=8, scoring="softmax", scale=1.0, bank=2),
}
MODELS["mellum_drawn"] = dict(MODELS["mellum"], drawn=True)
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
    "mellum_t1024": ("mellum", 1024, 1024, 700),
    "mellum_t2048": ("mellum", 2048, 1024, 700),
    "mellum_b32": ("mellum", 32, None, 24),
    # a short chunk, and the same with every pair drawn to this share (a
    # bias on its experts): 256 held pairs at a capacity of 128, two rounds
    "mellum_t32": ("mellum", 32, None, 32),
    "mellum_t32_drawn": ("mellum_drawn", 32, None, 32),
}
# What one expert layer's time is made of, beside the ``%gmm`` products.
ITEMS = ("xs gather", "body elementwise", "way back", "router pick",
         "router top-k", "router product and scores", "sorts", "counts",
         "gmm metadata", "packing", "script", "other")


def _weights(m):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    groups = m["held"] * (m["bank"] or 1)
    up = m["inner"] * (2 if m["gated"] else 1)
    bf = lambda k, s: (0.02 * jax.random.normal(k, s, jnp.float32)
                       ).astype(jnp.bfloat16)
    return dict(
        w_router=jax.random.normal(keys[0], (m["d"], m["scored"]), jnp.float32),
        bias=0.01 * jax.random.normal(keys[1], (m["scored"],), jnp.float32)
        + (jnp.arange(m["scored"]) < m["held"]) * (2.0 if m.get("drawn") else 0.0),
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
            w["bias"] if m["scoring"] == "sigmoid" or m.get("drawn") else None,
            body_of,
            top_k=m["top_k"], norm_topk_prob=True, scale=m["scale"],
            scoring=m["scoring"], held=m["held"], expert_first=0,
            token_budget=budget, **kw)
        with jax.named_scope("attrib_glue"):
            y = jnp.pad(y, ((0, 0), (0, m["d"] - m["k_in"])))
            h = u.astype(jnp.float32) + y
            h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
            return h.astype(jnp.bfloat16), stats

    return jax.lax.scan(layer, u, jnp.arange(LAYERS, dtype=jnp.int32))


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$", re.M)
_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def item_of(name, result, opcode, rest, m, tokens, packed):
    """The item one instruction of the compiled layer belongs to."""
    op = re.search(r'op_name="([^"]*)"', rest)
    op = op.group(1) if op else ""
    tail = op.rsplit("/", 1)[-1]
    shape = _SHAPE.search(result)
    dtype, dims = shape.groups() if shape else ("", "")
    dims = [int(d) for d in dims.split(",") if d]
    if "moe_sum_rows" in name or "moe_sum_rows" in op:
        return "way back"
    if "attrib_glue" in op:
        return "script"
    if "moe_experts" in op:
        if len(dims) == 2 and dims[0] >= 128 and dtype in ("bf16", "f32"):
            return "body elementwise"
        return "gmm metadata"
    if "moe_router" in op:
        if opcode == "sort" or tail in ("sort", "top_k", "argsort"):
            by_expert = len(dims) == 2 and dims[-1] == m["scored"]
            return "router top-k" if by_expert or tail == "top_k" else "sorts"
        if dtype == "f32" and dims and dims[-1] != m["scored"] and (
                len(dims) == 1 and dims[0] > tokens
                or len(dims) == 2 and dims[-1] == m["top_k"]):
            return "router pick"  # the chosen scores, and their norm
        if dtype in ("s32", "pred", "u32"):
            return "counts"
        return "router product and scores"
    if packed and (opcode == "sort" or dims[:1] == [tokens] and (
            tail in ("gather", "cumsum", "select_n"))):
        return "packing"
    if dtype == "bf16" and len(dims) == 2 and dims[1] == m["k_in"] and (
            dims[0] != tokens):
        return "xs gather"
    if dtype == "f32" and dims and dims[-1] == m["k_in"]:
        return "way back"
    return "other"


def items_of(compiled_text, per_layer, m, tokens, packed):
    """``per_layer`` {``%name shape``: us} summed by item. ``tokens``: the
    layer's tokens as routed (the budget where the step is packed)."""
    where = {}
    for name, result, opcode, rest in _INSTRUCTION.findall(compiled_text):
        where.setdefault(name, (result, opcode, rest))
    items = dict.fromkeys(ITEMS, 0.0)
    for op, us in per_layer.items():
        name = op.split(" ")[0]
        item = "other" if name not in where else item_of(
            name, *where[name], m, tokens, packed)
        items[item] += us
    return {k: round(v, 2) for k, v in items.items()}


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
        text = fn.lower(u, valid, w).compile().as_text()
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
        packed = budget is not None and budget < n
        report["shapes"][name] = {
            "wall_us_a_layer": round(wall_us, 2),
            "gmm_us_a_layer": round(sum(kernels.values()), 2),
            "other_ops_us_a_layer": round(sum(others.values()), 2),
            "held_pairs_a_layer": float(stats[0, 1]),
            "items_us_a_layer": items_of(
                text, others, m, budget if packed else n, packed),
            "finite": bool(jnp.isfinite(out[0].astype(jnp.float32)).all()),
            # the layers' output, to hold two trees' reports together
            "out_mean_abs": float(jnp.mean(jnp.abs(out[0].astype(jnp.float32)))),
            "out_head": [float(v) for v in out[0][0, :4].astype(jnp.float32)],
            "other_ops": {o: round(t, 2) for o, t in sorted(
                others.items(), key=lambda kv: -kv[1]) if t >= 0.3},
        }
        print(json.dumps({name: report["shapes"][name]}), flush=True)
    with open(os.path.join(OUT_DIR, f"{args.tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
