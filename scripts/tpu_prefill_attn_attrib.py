"""On-chip attribution of the prefill attention kernel's time, standalone.

Times ``%paged_attn_prefill`` alone at the shapes the benchmark's cells
run, as one jitted loop of ``--calls`` kernel calls over a stacked cache
with a traced layer index (the way the model's layer scan calls it; PR 32's
method for the decode kernel), and splits a call's time:

  full        the kernel as it is;
  copies      the same page copies, nothing folded;
  fold        the same fold on whatever the chunk buffer holds, no copy;
  split       full, with e4m3 pages left as they are and ``_pv_dot``'s
              split over the probability tile (candidate 1 taken out);
  astype      full, with the compiler's own e4m3 -> bf16 conversion in
              place of ``_widen_e4m3``;
  all_rows    full, with the padded query rows folded too (3 out).

The last three override functions of the module that a tree before PR 34
does not have, and are left out there. (Candidate 2, masks on boundary
chunks only, was timed by this script's first revision against a fold with
two bodies: PERF.md §6, PR 34.) One row a call:

``dense256`` / ``dense128``: 32 Q / 8 KV heads x 128, fp8 pages of 128
tokens, a 256-token bucket holding 129-191 real tokens (128: 65-128) at
the end of a context drawn as ``perf/traffic/sessions-closed.json`` draws
it (1,024 shared + a log-uniform 2,048-8,192 history + up to 2,000 of
turns: 3-11k). ``hist1024``: the same model, 1,024 real tokens in four
tiles over 0-9k (set-up's history prefill). ``hybrid256`` / ``hybrid1024``:
32 Q / 2 KV heads, bf16 pages, a fresh prompt's chunk: 129-256 (513-1,024)
real tokens over nothing.

Each line gives the time of one call and its share of the call's
arithmetic roofline: ``2 x 2 x 128`` operations a query row and column it
may see, over the bf16 peak of ``perf/peaks.json``, the rows counted both
as padded (what the kernel is handed) and as real (what the request
needs). ``--sub-rows`` and ``--chunk-tokens`` time other geometries (module
constants the script overrides; the program has no such option). Writes
``chiprun_out/prefill_attn_attrib[_<tag>].json``.

    python scripts/tpu_prefill_attn_attrib.py [--tag x] [--shapes dense256] [--variants full fold]
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
from production_stack_tpu.ops import paged_attention_pallas as pap  # noqa: E402

BS, HD, H = 128, 128, 32
SHAPES = {
    # name: bucket, real tokens, KV heads, page dtype, layers held, pages
    "dense256": dict(T=256, real=(129, 191), KH=8, dtype="float8_e4m3fn",
                     L=8, nb=1340, ctx="sessions"),
    "dense128": dict(T=128, real=(65, 128), KH=8, dtype="float8_e4m3fn",
                     L=8, nb=1340, ctx="sessions"),
    "hist1024": dict(T=1024, real=(1024, 1024), KH=8, dtype="float8_e4m3fn",
                     L=8, nb=1340, ctx="history"),
    "hybrid256": dict(T=256, real=(129, 256), KH=2, dtype="bfloat16",
                      L=1, nb=2048, ctx="fresh"),
    "hybrid1024": dict(T=1024, real=(513, 1024), KH=2, dtype="bfloat16",
                       L=1, nb=2048, ctx="fresh"),
}
NEEDS_HOOKS = ("split", "astype", "all_rows")


def draw(shape: dict, seed: int) -> "tuple[int, int]":
    """(tokens cached before the chunk, real tokens of the chunk)."""
    rng = np.random.default_rng(seed)
    real = int(rng.integers(shape["real"][0], shape["real"][1] + 1))
    if shape["ctx"] == "sessions":
        start = int(1024 + np.exp(rng.uniform(np.log(2048), np.log(8192)))
                    + rng.uniform(0, 2000))
    elif shape["ctx"] == "history":
        start = int(rng.integers(0, 9)) * 1024
    else:
        start = 0
    return start, real


def least_s(start: int, rows: int, kv_len: int, peak: float) -> float:
    """Least time of one layer's call: scores and ``p @ V`` of ``rows``
    query positions (``H`` heads each) over the columns each may see."""
    seen = np.minimum(start + np.arange(rows) + 1, kv_len).sum()
    return 2 * 2 * H * HD * int(seen) / peak


def _loop_without(what: str, orig):
    """``_page_dma_loop`` with the fold or the copies taken out."""
    if what == "copies":  # keep the copies, fold nothing
        def loop(**kw):
            return orig(**dict(kw, compute_chunk=lambda page, c: None))
        return loop

    def loop(**kw):  # keep the fold, copy nothing
        buf, fold, live = kw["buf"], kw["compute_chunk"], kw["live"]

        def body(c, _):
            # (a view of the pages, the position of their first column)
            fold(buf.at[jax.lax.rem(c, 2)], c * kw["chunk"] * buf.shape[3])
            return 0

        jax.lax.fori_loop(live.c_start, live.n_chunks, body, 0)
    return loop


def _overrides(variant: str) -> dict:
    if variant in ("copies", "fold"):
        return {"_page_dma_loop": _loop_without(variant, pap._page_dma_loop)}
    if variant == "split":
        return {"_fold_dtype": lambda kv_dtype, rows, hd: jnp.dtype(kv_dtype)}
    if variant == "astype":
        def halves(x8):
            x = x8.astype(jnp.bfloat16)
            return x[: x.shape[0] // 2], x[x.shape[0] // 2:]
        return {"_widen_e4m3": halves,
                "_widened_rows": lambda S: jax.lax.broadcasted_iota(
                    jnp.int32, (1, S), 1)}
    if variant == "all_rows":
        return {"_real_positions":
                lambda kv_len, first, q_tile: jnp.where(kv_len > 0, q_tile, 0)}
    return {}


_COMPILED: dict = {}  # one program a variant, shape and geometry


def time_variant(variant, q, kv, tables, lens, starts, calls, iters):
    L = kv.shape[0]
    scale = 1.0 / np.sqrt(HD)
    T = q.shape[1]
    key = (variant, q.shape, kv.shape, str(kv.dtype),
           getattr(pap, "_PREFILL_SUB_ROWS", 0), pap._PREFILL_CHUNK_TOKENS)
    overrides = _overrides(variant)
    saved = {k: getattr(pap, k) for k in overrides}
    for k, v in overrides.items():
        setattr(pap, k, v)
    try:
        def run(q, kv, tables, lens, starts):
            pos = starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None]

            def body(i, q):
                out = pap.pallas_paged_attention(
                    q, kv, tables, lens, pos, jax.lax.rem(i, L), scale=scale)
                # Chain the calls; ``fold`` reads a buffer nothing wrote,
                # so keep its (possibly non-finite) result out of q.
                return q + jnp.where(jnp.isfinite(out), out, 0) * 1e-3
            return jax.lax.fori_loop(0, calls, body, q)

        fn = _COMPILED.setdefault(key, jax.jit(run))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, kv, tables, lens, starts))
        first = time.perf_counter() - t0
    finally:
        for k, v in saved.items():
            setattr(pap, k, v)
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, kv, tables, lens, starts)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / iters / calls)
    return min(best), first


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="")
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--variants", nargs="+",
                    default=["full", "copies", "fold", "split", "astype",
                             "all_rows"])
    ap.add_argument("--sub-rows", nargs="+", type=int, default=[0])
    ap.add_argument("--chunk-tokens", nargs="+", type=int, default=[0])
    ap.add_argument("--seeds", nargs="+", type=int, default=[340001, 340002])
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    if resolve_platform() != "tpu":
        print("tpu_prefill_attn_attrib: backend is not tpu; a time from "
              "anything else is not a measurement", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peak = float(peaks[kind]["bf16_flops_per_s"])
    hooks = hasattr(pap, "_fold_dtype")
    report = {"device": describe_devices(), "hooks": hooks, "lines": []}
    for name in args.shapes:
        s = SHAPES[name]
        dtype = jnp.dtype(s["dtype"])
        lanes = s["KH"] * HD
        one = jax.jit(lambda k: jax.random.normal(
            k, (1, s["nb"], 2, BS, lanes), jnp.bfloat16).astype(dtype))(
                jax.random.PRNGKey(0))
        kv = jnp.concatenate([one] * s["L"], axis=0) if s["L"] > 1 else one
        for seed in args.seeds:
            rng = np.random.default_rng(seed)
            start, real = draw(s, seed)
            kv_len = start + real
            W = 128
            tables = jnp.asarray(rng.integers(
                0, s["nb"], (1, W)).astype(np.int32))
            q = jnp.asarray(rng.standard_normal(
                (1, s["T"], H, HD)), jnp.bfloat16)
            for sr, ct in [(a, b) for a in args.sub_rows
                           for b in args.chunk_tokens]:
                if sr and hooks:
                    pap._PREFILL_SUB_ROWS = sr
                if ct:
                    pap._PREFILL_CHUNK_TOKENS = ct
                for variant in args.variants:
                    if variant in NEEDS_HOOKS and not hooks:
                        continue
                    t, first = time_variant(
                        variant, q, kv, tables,
                        jnp.asarray([kv_len], jnp.int32),
                        jnp.asarray([start], jnp.int32),
                        args.calls, args.iters)
                    padded = least_s(start, s["T"], kv_len, peak)
                    needed = least_s(start, real, kv_len, peak)
                    line = {
                        "shape": name, "seed": seed, "variant": variant,
                        "start": start, "real": real, "T": s["T"],
                        "sub_rows": getattr(pap, "_PREFILL_SUB_ROWS", 0),
                        "chunk_tokens": pap._PREFILL_CHUNK_TOKENS,
                        "call_us": round(t * 1e6, 2),
                        "least_padded_us": round(padded * 1e6, 2),
                        "least_real_us": round(needed * 1e6, 2),
                        "roofline_padded_pct": round(100 * padded / t, 2),
                        "roofline_real_pct": round(100 * needed / t, 2),
                        "first_call_s": round(first, 2),
                    }
                    report["lines"].append(line)
                    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    out = "prefill_attn_attrib" + (f"_{args.tag}" if args.tag else "")
    with open(f"chiprun_out/{out}.json", "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
