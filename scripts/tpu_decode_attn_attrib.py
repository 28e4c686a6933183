"""On-chip attribution of the decode attention kernel's time, standalone.

Times ``%paged_attn_decode`` alone at the two shapes the benchmark's cells
run, as one jitted loop of ``--calls`` kernel calls over a stacked cache
with a traced layer index (the way the model's layer scan calls it), and
splits a call's time four ways (PR 28's method for the int4 kernel):

  full      the kernel as it is;
  copies    the same page copies, nothing folded (``compute_chunk`` a no-op);
  fold      the same fold on whatever the chunk buffer holds, no copy at all;
  aligned   the kernel as it is, every length rounded to whole chunks.

``dense``: B = 16, 32 Q / 8 KV heads x 128, fp8 pages of 128 tokens, lengths
as ``perf/traffic/sessions-closed.json`` draws them (1,024 shared + a
log-uniform 2,048-8,192 history + up to 2,000 of turns: 3-11k).
``hybrid``: B = 32, 32 Q / 2 KV heads x 128, bf16 pages, 1-2.5k.
``verify_global`` / ``verify_window`` (PR 54): the verify-and-draft step of
``k-exaone-ep8-cut.thinking-closed``, B = 64 rows x 2 query positions, 64 Q
/ 8 KV heads x 128, bf16 pages, 2.3-7.2k of context behind 1,024 shared
tokens (the rows' tables agree on those eight pages); the window call reads
129 tokens a row. Since PR 54 such a call is ``%paged_attn_short`` (the
decode stream); on a tree before it, copied into its ``scripts/``, the same
lines time ``%paged_attn_prefill`` at a tile of two.

Each line gives the time of one call, of one grid cell, and the share of
the HBM roofline (``perf/cost/paged_attn.py``'s bytes: every row's live
keys and values once, the queries in and the result out, at the peak of
``perf/peaks.json``; ``distinct_pct``: the same with the shared tokens
counted once a call, what the shared phase reads). ``--chunk-tokens``, ``--fold-tokens`` and ``--slots``
time other geometries (module constants the script overrides; the program
has no such option). Writes ``chiprun_out/decode_attn_attrib[_<tag>].json``.

    python scripts/tpu_decode_attn_attrib.py [--tag x] [--chunk-tokens 1024 2048] [--slots 2 3]
"""

from __future__ import annotations

import argparse
import functools
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
    # name: rows, KV heads, page dtype, table widths, layers held, pages
    "dense": dict(B=16, KH=8, dtype="float8_e4m3fn", widths=(128, 64),
                  L=8, nb=1340),
    "hybrid": dict(B=32, KH=2, dtype="bfloat16", widths=(32,), L=1, nb=2048),
    # (H: query heads, T: query positions a row, shared: tokens of one prompt)
    "verify_global": dict(B=64, KH=8, dtype="bfloat16", widths=(64,), L=2,
                          nb=1800, H=64, T=2, shared=1024),
    "verify_window": dict(B=64, KH=8, dtype="bfloat16", widths=(64,), L=2,
                          nb=1800, H=64, T=2, shared=1024, window=128),
}


def draw_lengths(shape: str, B: int, W: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if shape == "dense":
        hist = np.exp(rng.uniform(np.log(2048), np.log(8192), B))
        lens = 1024 + hist + rng.uniform(0, 2000, B)
    elif shape.startswith("verify"):
        # perf/traffic/thinking-closed.json, a row caught mid-answer
        prompt = np.exp(rng.uniform(np.log(256), np.log(3072), B))
        lens = 1024 + prompt + rng.uniform(0, 1, B) * rng.uniform(1024, 3072, B)
    else:
        lens = np.exp(rng.uniform(np.log(128), np.log(1024), B)) + rng.uniform(
            512, 1536, B)
    return np.minimum(lens.astype(np.int64), W * BS - 1).astype(np.int32)


def roofline_s(tokens: int, rows: int, KH: int, kv_bytes: int, peak: float,
               lines: int = H) -> float:
    """Least time of one layer's call that reads ``tokens`` keys and values
    (``perf/cost/paged_attn.py``)."""
    nbytes = tokens * 2 * KH * HD * kv_bytes + rows * lines * HD * 2 * 2
    return nbytes / peak


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


def time_variant(variant, q, kv, tables, lens, calls, iters, window=0):
    L, T = kv.shape[0], q.shape[1]
    scale = 1.0 / np.sqrt(HD)
    orig = pap._page_dma_loop
    if variant in ("copies", "fold"):
        pap._page_dma_loop = _loop_without(variant, orig)
    try:
        def run(q, kv, tables, lens):
            def body(i, q):
                out = pap.pallas_paged_attention(
                    q, kv, tables, lens,
                    (lens - T)[:, None] + jnp.arange(T, dtype=jnp.int32),
                    jax.lax.rem(i, L), scale=scale, window=window)
                # Chain the calls; ``fold`` reads a buffer nothing wrote,
                # so keep its (possibly non-finite) result out of q.
                return q + jnp.where(jnp.isfinite(out), out, 0) * 1e-3
            return jax.lax.fori_loop(0, calls, body, q)

        fn = jax.jit(run)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, kv, tables, lens))
        first = time.perf_counter() - t0
    finally:
        pap._page_dma_loop = orig
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, kv, tables, lens)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / iters / calls)
    return min(best), first


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="")
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--variants", nargs="+",
                    default=["full", "copies", "fold", "aligned"])
    ap.add_argument("--chunk-tokens", nargs="+", type=int, default=[0])
    ap.add_argument("--fold-tokens", nargs="+", type=int, default=[0])
    ap.add_argument("--slots", nargs="+", type=int, default=[0])
    ap.add_argument("--widths", nargs="+", type=int, default=[],
                    help="table widths, instead of each shape's own")
    ap.add_argument("--seeds", nargs="+", type=int, default=[320001, 320002])
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    if resolve_platform() != "tpu":
        print("tpu_decode_attn_attrib: backend is not tpu; a time from "
              "anything else is not a measurement", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "peaks.json")) as f:
        peaks = json.load(f)
    kind = jax.devices()[0].device_kind
    peak = float(peaks[kind]["hbm_bytes_per_s"])
    report = {"device": describe_devices(), "lines": []}
    for shape in args.shapes:
        s = SHAPES[shape]
        dtype = jnp.dtype(s["dtype"])
        lanes = s["KH"] * HD
        key = jax.random.PRNGKey(0)
        one = jax.jit(lambda k: jax.random.normal(
            k, (1, s["nb"], 2, BS, lanes), jnp.bfloat16).astype(dtype))(key)
        kv = jnp.concatenate([one] * s["L"], axis=0) if s["L"] > 1 else one
        for W in args.widths or s["widths"]:
            for seed in args.seeds:
                rng = np.random.default_rng(seed)
                lens0 = draw_lengths(shape, s["B"], W, seed)
                tables = rng.integers(0, s["nb"], (s["B"], W)).astype(np.int32)
                shared = s.get("shared", 0)
                tables[:, : shared // BS] = tables[0, : shared // BS]
                tables = jnp.asarray(tables)
                heads, T = s.get("H", H), s.get("T", 1)
                window = s.get("window", 0)
                q = jnp.asarray(rng.standard_normal(
                    (s["B"], T, heads, HD)), jnp.bfloat16)
                for ct, ft, ns in [(c, f, n) for c in args.chunk_tokens
                                   for f in args.fold_tokens
                                   for n in args.slots]:
                    if ct:
                        pap._DECODE_CHUNK_TOKENS = ct
                    if ft:
                        pap._DECODE_FOLD_TOKENS = ft
                    if ns:
                        pap._DECODE_SLOTS = ns
                    span = pap._DECODE_CHUNK_TOKENS
                    for variant in args.variants:
                        lens = lens0
                        if variant == "aligned":
                            lens = np.maximum(
                                (lens0 + span // 2) // span * span, span
                            ).astype(np.int32)
                            lens = np.minimum(lens, W * BS // span * span)
                        t, first = time_variant(
                            variant, q, kv, tables, jnp.asarray(lens),
                            args.calls, args.iters, window)
                        read = (np.minimum(lens, window + T - 1) if window
                                else lens)
                        cost = functools.partial(
                            roofline_s, rows=s["B"], KH=s["KH"],
                            kv_bytes=dtype.itemsize, peak=peak,
                            lines=T * heads)
                        least = cost(int(read.sum()))
                        distinct = int(read.sum()) - (
                            0 if window else (s["B"] - 1) * shared)
                        line = {
                            "shape": shape, "W": W, "seed": seed,
                            "chunk_tokens": pap._DECODE_CHUNK_TOKENS,
                            "fold_tokens": pap._DECODE_FOLD_TOKENS,
                            "slots": pap._DECODE_SLOTS,
                            "variant": variant,
                            "kv_tokens": int(lens.sum()),
                            "distinct_pct": round(
                                100 * cost(distinct) / t, 2),
                            "call_us": round(t * 1e6, 2),
                            "cell_us": round(t * 1e6 / s["B"], 3),
                            "least_us": round(least * 1e6, 2),
                            "roofline_pct": round(100 * least / t, 2),
                            "first_call_s": round(first, 2),
                        }
                        report["lines"].append(line)
                        print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "decode_attn_attrib" + (f"_{args.tag}" if args.tag else "")
    with open(f"chiprun_out/{name}.json", "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
