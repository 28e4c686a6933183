"""What the convolution tails' path costs a decode layer on the chip, at the
three hybrids' widths.

    chiprun -- python scripts/tpu_conv_tail_attrib.py [--tag x] [--shapes qwen_kernel ...]

A decode step of a layer with a short causal convolution reads each row's
tail (the last ``taps - 1`` pre-activation rows) from a pool by the row's
slot, convolves ``[tail | this step's row]``, and writes the shifted tail
back. Three models keep such a pool, each in its own layout:

- ``qwen3-next-ep8-cut`` (``models/qwen3_next.py``): 12 layers x 73 slots x
  3 x 8,192 bf16, 64 rows a step. ``qwen_xla`` is the path it had until
  PR 43 (``tails[li, slots]`` and ``tails.at[li, slots].set`` on ``[layers,
  slots, 3 x C]``: ``ops/gated_delta.py::conv_tail_reference``, which a
  prefill step still runs, on the old layout); ``qwen_kernel`` is
  ``conv_tail_decode`` on ``[layers, slots, 3, C / 128, 128]``.
- ``nemotron-3-super-ep4-cut`` (``models/nemotron_h.py::_mamba``): 5 x 37 x
  ``[3, 10240]``, 32 rows, a bias.
- ``phi-4-mini-flash`` (``models/phi4flash.py::_mamba``): 9 x 37 x 3 x
  5,120 as one row, 32 rows, a bias.

Each is a scan over the pool's layers with the pool carried and donated, as
the models' step programs carry it; a layer is the tails' path and a
normalisation that feeds the next (no projection: the path is what is
timed). The scan is traced with the JAX profiler and the device's ``XLA
Ops`` are summed by name; times are per layer in microseconds, the mean over
every layer of every traced repeat. ``pool_shaped_us`` sums the operations
whose result has the pool's shape (a copy of it through fast memory, a
scatter over it, the kernel's own call).

Reads the capture with the benchmark's ``perf/trace.py``; report in
``chiprun_out/conv_tail_attrib/<tag>.json``.
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
from production_stack_tpu.ops import gated_delta as gdn  # noqa: E402

REPEATS = 8
OUT_DIR = os.path.join("chiprun_out", "conv_tail_attrib")
LANES = 128

# name -> layers, slots (the last is the scratch), channels, taps, rows of a
# decode step, the pool's layout, a bias or none
SHAPES = {
    "qwen_xla": dict(L=12, S=73, C=8192, taps=4, rows=64, form="row", bias=False),
    "qwen_kernel": dict(L=12, S=73, C=8192, taps=4, rows=64, form="kernel",
                        bias=False),
    "nemotron_xla": dict(L=5, S=37, C=10240, taps=4, rows=32, form="taps",
                         bias=True),
    "phi_xla": dict(L=9, S=37, C=5120, taps=4, rows=32, form="row", bias=True),
}


def pool_shape(m):
    n, C = m["taps"] - 1, m["C"]
    return (m["L"], m["S"]) + {
        "row": (n * C,), "taps": (n, C), "kernel": (n, C // LANES, LANES),
    }[m["form"]]


def layers_fn(m, tails, u, slots, keep, true_len, w, bias):
    """Every layer of the pool in a scan, each fed by the one before."""
    def layer(carry, li):
        u, tails = carry
        if m["form"] == "kernel":
            conv, tails = gdn.conv_tail_decode(tails, li, slots, keep, u, w[li])
        else:  # the models' own lines: gather, window, sum, shift, scatter
            conv, tails = gdn.conv_tail_reference(
                tails, li, slots, keep, true_len, u[:, None], w[li])
            conv = conv[:, 0]
        if bias is not None:
            conv = conv + bias[li]
        y = jax.nn.silu(conv)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
        return (y.astype(u.dtype), tails), None

    (u, tails), _ = jax.lax.scan(
        layer, (u, tails), jnp.arange(m["L"], dtype=jnp.int32))
    return u, tails


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
        print("tpu_conv_tail_attrib: no chip; times of the interpreted "
              "kernel say nothing", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    report = {"device": dev.device_kind, "shapes": {}}
    bf16 = jnp.bfloat16
    for name in args.shapes:
        m = SHAPES[name]
        L, S, C, B = m["L"], m["S"], m["C"], m["rows"]
        ks = jax.random.split(jax.random.PRNGKey(C + B), 5)
        shape = pool_shape(m)
        tails = jax.random.normal(ks[0], shape, bf16)
        u = jax.random.normal(ks[1], (B, C), bf16)
        w = jax.random.normal(ks[2], (L, m["taps"], C), bf16)
        bias = jax.random.normal(ks[3], (L, C), bf16) if m["bias"] else None
        # distinct slots, the last two rows padding at the scratch slot
        real = jnp.arange(B) < B - 2
        slots = jnp.where(
            real, jax.random.permutation(ks[4], S - 1)[:B], S - 1
        ).astype(jnp.int32)
        keep = (jnp.arange(B) % 5 != 1) & real
        true_len = real.astype(jnp.int32)
        fn = jax.jit(
            lambda tails, u, m=m: layers_fn(
                m, tails, u, slots, keep, true_len, w, bias),
            donate_argnums=(0,))
        pool_text = "bf16[" + ",".join(map(str, shape)) + "]"
        text = fn.lower(tails, u).compile().as_text()
        out, tails = jax.block_until_ready(fn(tails, u))
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out, tails = fn(tails, out)
        jax.block_until_ready(out)
        wall_us = (time.perf_counter() - t0) / REPEATS / L * 1e6
        trace_dir = os.path.join(OUT_DIR, f"trace_{args.tag}_{name}")
        with jax.profiler.trace(trace_dir):
            for _ in range(REPEATS):
                out, tails = fn(tails, out)
            jax.block_until_ready(out)
        per_layer = {o: s / REPEATS / L * 1e6
                     for o, s in device_ops(trace_dir).items()}
        shutil.rmtree(trace_dir)
        pooled = {o: t for o, t in per_layer.items()
                  if pool_text in o or "conv_tail_decode" in o}
        report["shapes"][name] = {
            "pool": pool_text,
            "pool_mb": round(tails.size * 2 / 1e6, 1),
            "moved_mb_a_layer": round(B * 2 * (m["taps"] - 1) * C * 2 / 1e6, 2),
            "wall_us_a_layer": round(wall_us, 2),
            "device_us_a_layer": round(sum(per_layer.values()), 2),
            "pool_shaped_us_a_layer": round(sum(pooled.values()), 2),
            "pool_in_fast_memory": bool(
                re.search(re.escape(pool_text) + r"\S*S\(1\)", text)),
            "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
            "ops": {o: round(t, 2) for o, t in sorted(
                per_layer.items(), key=lambda kv: -kv[1])[:24]},
        }
        print(json.dumps({name: report["shapes"][name]}), flush=True)
        del tails, fn
    with open(os.path.join(OUT_DIR, f"{args.tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
