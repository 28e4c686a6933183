"""What a layer's int4 matmuls cost on the chip, and what XLA runs around them.

    chiprun -- python scripts/tpu_int4_attrib.py [--rows 16 256] [--tag x]

One layer's seven projections at the dense cell's widths (hidden 4096, KV
1024, FFN 14336), called as ``models/llama.py`` calls them: six through
``int4_matmul_stacked`` on a stack of layers and ``wk`` through the 2-D
``int4_matmul`` on a scan slice, bf16 activations in, a norm-like fusion
between them so that every call's ``x`` has a producer. A scan over the
layers is traced with the JAX profiler, and the device's ``XLA Ops`` are
summed by name: the kernels' own times, and every other operation of the
loop body (which is where the hand-over of ``x`` to a call shows: PERF.md
§6, PR 37). Times are per layer, in microseconds, the mean over every layer
of every traced repeat.

The script imports nothing of the program but the two public entries (and
the benchmark's ``perf/trace.py`` to read the capture), so a copy of it runs
on a tree whose kernel takes ``x`` another way: copy it into that tree's
``scripts/`` and compare the two reports
(``chiprun_out/int4_attrib/<tag>.json``).
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
from production_stack_tpu.ops.int4_matmul import (  # noqa: E402
    int4_matmul,
    int4_matmul_stacked,
)

HIDDEN, KV, FFN = 4096, 1024, 14336
LAYERS = 8
REPEATS = 8
OUT_DIR = os.path.join("chiprun_out", "int4_attrib")


def _leaf(key, din, dout):
    packed = jax.random.randint(
        key, (LAYERS, din // 2, dout), -128, 128, jnp.int8)
    scales = jnp.full((LAYERS, din // 128, dout), 1e-3, jnp.float32)
    return packed, scales


def _weights():
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    shapes = {"wq": (HIDDEN, HIDDEN), "wk": (HIDDEN, KV), "wv": (HIDDEN, KV),
              "wo": (HIDDEN, HIDDEN), "w_gate": (HIDDEN, FFN),
              "w_up": (HIDDEN, FFN), "w_down": (FFN, HIDDEN)}
    return {n: _leaf(k, *s) for k, (n, s) in zip(keys, shapes.items())}


def _norm(x):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
            ).astype(jnp.bfloat16)


def layers_fn(x, w):
    """The seven calls a layer, scanned over the stack as the model's layer
    scan is: ``wk`` a sliced leaf (``xs``), the other six whole."""
    whole = {n: v for n, v in w.items() if n != "wk"}

    def body(x, per_layer):
        li, wk = per_layer
        stacked = lambda a, n: int4_matmul_stacked(a, *whole[n], li)
        h = _norm(x)
        q, v = stacked(h, "wq"), stacked(h, "wv")
        k = int4_matmul(h, *wk)
        attn = (q + jnp.tile(k + v, (1, HIDDEN // KV))).astype(jnp.bfloat16)
        x = x + stacked(attn, "wo").astype(jnp.bfloat16)
        h = _norm(x)
        ff = (jax.nn.silu(stacked(h, "w_gate")) * stacked(h, "w_up")
              ).astype(jnp.bfloat16)
        return x + stacked(ff, "w_down").astype(jnp.bfloat16), None

    return jax.lax.scan(
        body, x, (jnp.arange(LAYERS, dtype=jnp.int32), w["wk"]))[0]


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
    ap.add_argument("--rows", type=int, nargs="+", default=[16, 256])
    ap.add_argument("--tag", default="change")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("tpu_int4_attrib: no chip; times of the interpreted kernel "
              "say nothing", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    w = _weights()
    report = {"device": dev.device_kind, "layers": LAYERS, "rows": {}}
    fn = jax.jit(layers_fn)
    for rows in args.rows:
        x = jax.random.normal(
            jax.random.PRNGKey(rows), (rows, HIDDEN), jnp.bfloat16)
        jax.block_until_ready(fn(x, w))
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(x, w)
        jax.block_until_ready(out)
        wall_us = (time.perf_counter() - t0) / REPEATS / LAYERS * 1e6
        trace_dir = os.path.join(OUT_DIR, f"trace_{args.tag}_{rows}")
        with jax.profiler.trace(trace_dir):
            for _ in range(REPEATS):
                out = fn(x, w)
            jax.block_until_ready(out)
        per_layer = {n: s / REPEATS / LAYERS * 1e6
                     for n, s in device_ops(trace_dir).items()}
        shutil.rmtree(trace_dir)
        kernels = {n: t for n, t in per_layer.items()
                   if n.startswith("%int4_matmul")}
        others = {n: t for n, t in per_layer.items() if n not in kernels}
        report["rows"][rows] = {
            "wall_us_a_layer": round(wall_us, 2),
            "kernels_us_a_layer": round(sum(kernels.values()), 2),
            "other_ops_us_a_layer": round(sum(others.values()), 2),
            "kernels": {n: round(t, 2) for n, t in sorted(kernels.items())},
            "other_ops": {n: round(t, 2) for n, t in sorted(
                others.items(), key=lambda kv: -kv[1])[:24]},
        }
        print(json.dumps({rows: report["rows"][rows]}), flush=True)
    with open(os.path.join(OUT_DIR, f"{args.tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
