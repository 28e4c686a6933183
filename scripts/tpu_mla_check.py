"""On-chip timing of latent attention (``ops/mla_attention.py``), standalone,
at the published widths of ``perf/configs/glm-4.7-flash-pp6-cut.json``
(20 heads, rank 512 + 64 rotary lanes in rows of 640, bf16 pages of 128).

``decode``: ``%mla_decode`` alone at the latent cell's shape — 16 rows of
which 12 hold a document of 16,384-40,960 tokens (the mix's own multiset)
plus up to 2,500 of turns — as one jitted loop of ``--calls`` kernel calls
over a stacked cache with a traced layer index, split as
``scripts/tpu_decode_attn_attrib.py`` splits the K+V kernel:

  full      the kernel as it is;
  copies    the same page copies, nothing folded;
  fold      the same fold on whatever the chunk buffer holds, no copy at all.

Each line gives a call's time and its share of the roofline of
``perf/cost/mla_decode.py`` (every live row's 576 stored elements once,
queries in and results out, against ``2 x tokens x heads x (2 x 512 + 64)``
operations at the bf16 peak). ``--chunk-tokens``, ``--fold-tokens`` and
``--slots`` time other geometries.

``prefill``: one layer's attention of a prefill chunk both ways through the
same pages — expanded (keys and values rebuilt from the latents a block at
a time) and absorbed (``W_uk`` on the queries, scores against the latents,
``W_uv`` on the result) — at (fresh tokens, cached tokens) pairs: the
crossover that ``models/glm4_moe_lite.py::prefill_path`` encodes.

Writes ``chiprun_out/mla_check[_<tag>].json``.

    python scripts/tpu_mla_check.py decode prefill [--tag x]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.device import describe_devices, resolve_platform  # noqa: E402
from production_stack_tpu.ops import mla_attention as mla  # noqa: E402
from tpu_decode_attn_attrib import _loop_without  # noqa: E402  (scripts/)

H, RANK, ROPE, NOPE, DV, BS, LANES = 20, 512, 64, 192, 256, 128, 640
SCALE = 1.0 / 16.0


def cell_lengths(seed: int, rows: int = 16, live: int = 12) -> np.ndarray:
    """The mix's documents (evenly spaced log-uniform quantiles) plus what
    the turns so far added, in ``rows`` rows of which ``live`` are used."""
    rng = np.random.default_rng(seed)
    docs = np.exp(np.log(16384) + (np.arange(live) + 0.5) / live
                  * (np.log(40960) - np.log(16384)))
    lens = np.zeros(rows, np.int64)
    lens[rng.permutation(rows)[:live]] = docs + rng.uniform(100, 2500, live)
    return lens.astype(np.int32)


def least_s(lens: np.ndarray, peaks: dict) -> float:
    tokens, rows = int(lens.sum()), int((lens > 0).sum())
    nbytes = tokens * (RANK + ROPE) * 2 + rows * H * (RANK + ROPE + RANK) * 2
    flops = 2.0 * tokens * H * (2 * RANK + ROPE)
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops_per_s"])


def time_decode(variant, q, kv, tables, lens, calls, iters, geometry):
    L = kv.shape[0]
    orig = mla._page_dma_loop
    if variant in ("copies", "fold"):
        mla._page_dma_loop = _loop_without(variant, orig)
    try:
        def run(q, kv, tables, lens):
            def body(i, q):
                out = mla.mla_decode(
                    q, kv, tables, lens, jax.lax.rem(i, L), rank=RANK,
                    scale=SCALE, **geometry)
                out = jnp.pad(out, ((0, 0), (0, 0), (0, ROPE)))
                return q + jnp.where(jnp.isfinite(out), out, 0) * 1e-3
            return jax.lax.fori_loop(0, calls, body, q)

        fn = jax.jit(run)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, kv, tables, lens))
        first = time.perf_counter() - t0
    finally:
        mla._page_dma_loop = orig
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, kv, tables, lens)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / iters / calls)
    return min(best), first


def decode(args, peaks, report):
    L, nb, W = 8, 1024, 512
    one = jax.jit(lambda k: jax.random.normal(
        k, (1, nb, 1, BS, LANES), jnp.bfloat16))(jax.random.PRNGKey(0))
    kv = jnp.concatenate([one] * L, axis=0)
    for seed in args.seeds:
        rng = np.random.default_rng(seed)
        lens = cell_lengths(seed)
        tables = jnp.asarray(rng.integers(0, nb, (len(lens), W)).astype(np.int32))
        q = jnp.asarray(rng.standard_normal((len(lens), H, RANK + ROPE)),
                        jnp.bfloat16)
        for ct in args.chunk_tokens:
            for ft in args.fold_tokens:
                for ns in args.slots:
                    geometry = {k: v for k, v in (
                        ("chunk_tokens", ct), ("fold_tokens", ft),
                        ("slots", ns)) if v}
                    for variant in args.variants:
                        t, first = time_decode(
                            variant, q, kv, tables, jnp.asarray(lens),
                            args.calls, args.iters, geometry)
                        least = least_s(lens, peaks)
                        line = {
                            "what": "decode", "seed": seed, "variant": variant,
                            **geometry, "kv_tokens": int(lens.sum()),
                            "call_us": round(t * 1e6, 2),
                            "per_1024_tokens_us": round(
                                t * 1e6 / lens.sum() * 1024, 3),
                            "least_us": round(least * 1e6, 2),
                            "roofline_pct": round(100 * least / t, 2),
                            "first_call_s": round(first, 2),
                        }
                        report["lines"].append(line)
                        print(json.dumps(line), flush=True)


def prefill(args, peaks, report):
    f32 = jnp.float32
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    w_uk = (jax.random.normal(key[0], (H, NOPE, RANK), f32) / np.sqrt(RANK)
            ).astype(jnp.bfloat16)
    w_uv = (jax.random.normal(key[1], (H, RANK, DV), f32) / np.sqrt(RANK)
            ).astype(jnp.bfloat16)

    def expanded(q_nope, q_rope, cache, tables, lens, pos):
        return mla.expanded_attention(
            q_nope, q_rope, w_uk, w_uv, cache, 0, tables, lens, pos,
            scale=SCALE).astype(jnp.bfloat16)

    def absorbed(q_nope, q_rope, cache, tables, lens, pos):
        q_abs = jnp.concatenate([
            jnp.einsum("bthn,hnc->bthc", q_nope, w_uk,
                       preferred_element_type=f32).astype(jnp.bfloat16),
            q_rope], axis=-1)
        o = mla.absorbed_attention(q_abs, cache, 0, tables, lens, pos,
                                   rank=RANK, scale=SCALE)
        return jnp.einsum("bthc,hcv->bthv", o.astype(jnp.bfloat16), w_uv,
                          preferred_element_type=f32).astype(jnp.bfloat16)

    for rows, fresh, cached in args.shapes:
        total = fresh + cached
        W = -(-total // BS)
        nb = rows * W + 1
        cache = jax.jit(lambda k: jax.random.normal(
            k, (1, nb, 1, BS, LANES), jnp.bfloat16))(key[2])
        tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(rows, W)
        lens = jnp.full((rows,), total, jnp.int32)
        pos = jnp.tile(cached + jnp.arange(fresh, dtype=jnp.int32), (rows, 1))
        q_nope = jax.random.normal(key[3], (rows, fresh, H, NOPE), jnp.bfloat16)
        q_rope = jax.random.normal(key[3], (rows, fresh, H, ROPE), jnp.bfloat16)
        outs = {}
        for name, fn in (("expanded", expanded), ("absorbed", absorbed)):
            jit = jax.jit(fn)
            t0 = time.perf_counter()
            outs[name] = jax.block_until_ready(
                jit(q_nope, q_rope, cache, tables, lens, pos))
            first = time.perf_counter() - t0
            best = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = jit(q_nope, q_rope, cache, tables, lens, pos)
                jax.block_until_ready(out)
                best.append((time.perf_counter() - t0) / args.iters)
            pairs = rows * H * fresh * (cached + (fresh + 1) / 2)
            flops = {"expanded": 2 * pairs * (NOPE + ROPE + DV)
                     + 2.0 * rows * total * H * (NOPE + DV) * RANK,
                     "absorbed": 2 * pairs * (2 * RANK + ROPE)
                     + 2.0 * rows * fresh * H * (NOPE + DV) * RANK}[name]
            line = {"what": "prefill", "path": name, "rows": rows,
                    "fresh": fresh, "cached": cached,
                    "layer_ms": round(min(best) * 1e3, 3),
                    "least_ms": round(flops / peaks["bf16_flops_per_s"] * 1e3, 3),
                    "first_call_s": round(first, 2)}
            report["lines"].append(line)
            print(json.dumps(line), flush=True)
        diff = float(jnp.abs(outs["expanded"].astype(f32)
                             - outs["absorbed"].astype(f32)).max())
        print(json.dumps({"what": "prefill", "rows": rows, "fresh": fresh,
                          "cached": cached, "paths_max_abs_diff": diff}),
              flush=True)


def _shape(text: str):
    rows, fresh, cached = (int(x) for x in text.split("x"))
    return rows, fresh, cached


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", nargs="+", choices=["decode", "prefill"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--variants", nargs="+", default=["full", "copies", "fold"])
    ap.add_argument("--chunk-tokens", nargs="+", type=int, default=[0])
    ap.add_argument("--fold-tokens", nargs="+", type=int, default=[0])
    ap.add_argument("--slots", nargs="+", type=int, default=[0])
    ap.add_argument("--seeds", nargs="+", type=int, default=[330001, 330002])
    ap.add_argument("--shapes", nargs="+", type=_shape, default=[
        (1, 1024, 0), (1, 1024, 32768), (1, 64, 32768), (1, 256, 32768),
        (1, 512, 32768), (4, 128, 32768)],
        help="prefill: rows x fresh x cached, e.g. 1x1024x32768")
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if resolve_platform() != "tpu":
        print("tpu_mla_check: backend is not tpu; a time from anything else "
              "is not a measurement", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "peaks.json")) as f:
        peaks = {k: float(v) for k, v in json.load(f)[
            jax.devices()[0].device_kind].items()
            if isinstance(v, (int, float))}
    report = {"device": describe_devices(), "lines": []}
    if "decode" in args.what:
        decode(args, peaks, report)
    if "prefill" in args.what:
        prefill(args, peaks, report)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "mla_check" + (f"_{args.tag}" if args.tag else "")
    with open(f"chiprun_out/{name}.json", "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
