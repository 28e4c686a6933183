"""On-chip check: every Pallas kernel, compiled, against its XLA reference.

At the Llama-3-8B layer shapes the serving path uses (32 Q / 8 KV heads x
128, 128-token pages, the 32k-context table of 256 pages, ~20k live tokens):

  - paged attention, decode (T=1, batch 8) and one 1024-token prefill chunk
    at the end of a ~20k-token history, over bf16 and fp8 (e4m3) pages, plus
    a sliding-window + soft-cap variant of each — reference: the gather
    implementation (``ops/attention.py``), in 256-row slices for prefill so
    its score tensor fits;
  - decode at the two shapes the benchmark's cells run, on a stacked cache
    at layer 2 of 3: 16 rows over 8 KV heads of fp8 pages at 3-11k of
    context as drawn (ragged, two rows empty), and 32 rows over 2 KV heads
    of bf16 pages at 1-2.5k; prefill as the runner pads it: a 256-token
    bucket holding 150 real tokens over 8k of fp8 context, and a 1,024-token
    one holding 600 over 4k with a window and a soft cap (the padding rows
    must come back as zeros). Every table entry past a row's last live page
    points at a page of NaN, and the other layers are NaN: a dead page that
    is fetched, a column that is not masked, or VMEM that nothing wrote
    reaching ``p @ V`` shows here and only here (the interpreter's buffers
    start clean);
  - decode behind one shared prompt (``shared_run_case``: 16 rows behind
    512 shared tokens at the looped cell's geometry, 16 behind 1,024 at the
    dense cell's), checked the same way and timed a call against what the
    chip's memory allows for the rows' contexts and for the distinct tokens
    among them;
  - ``mla_decode`` (``ops/mla_attention.py``: absorbed latent attention, 20
    heads over one ``[c_kv 512 | k_rope 64 | 0]`` row a token, bf16 pages)
    at the latent cell's shape, 16 rows of which four are empty at 16-41k
    of context, and at ragged lengths on every side of a page, a fold and
    a chunk (1 to 6,145, the last chunk shorter than a fold), both on a
    stacked cache at layer 2 of 3 with NaN in the other layers, in the
    page every dead table entry points at and in the padding lanes of
    that page — reference: gather the live rows, softmax in float32;
  - ``conv_tail_decode`` (``ops/gated_delta.py``) at the gated-delta cell's
    widths against the ``jax.numpy`` gather, shift and scatter, and that
    cell's compiled step programs read as text: a decode step holds the
    tails' pool in no instruction but the kernel's call, a prefill step in
    no copy (``qwen3next_program_tails_*``);
  - the fused decode-write variant behind ``PST_FUSED_KV_WRITE`` (each cell
    warms up its own first chunk) — reference: XLA scatter + gather
    attention, and the written cache rows; ``ragged`` gives its rows the
    lengths 1 to 20k so that a row's write page lies in its first chunk;
  - ``int4_matmul`` at decode-width (8) and prefill-width (1024) rows for
    4096->14336 and 14336->4096 — reference: ``dequant_int4`` + fp32 dot;
  - ``int4_matmul_stacked`` (the call the model makes: three layers' weights
    stacked, layer 2 asked for) at 16 and 256 rows for the same two shapes,
    at 64 and 128 rows for both (one row count on each side of the kernel's
    tile rule: whole output width up to 64 rows, column tiles above), and at
    16 rows for the attention projections 4096->4096 and 4096->1024 — same
    reference on that layer's weights, and bit for bit the 2-D entry's
    result on ``packed[2]``, ``scales[2]``.

Prints one line per case with the max abs difference, then one JSON object
(also written to ``chiprun_out/kernel_check.json``). Exits non-zero if the
backend is not ``tpu``, a kernel fails to compile, or a difference exceeds
its bound. Run from the checkout root, on the chip:

    python scripts/tpu_kernel_check.py [substring of a case's name ...]
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
import traceback
import zlib

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.device import describe_devices, resolve_platform  # noqa: E402
from production_stack_tpu.models.llama import (  # noqa: E402
    dequant_int4,
    quantize_leaf_int4,
)
from production_stack_tpu.ops.attention import gather_paged_attention  # noqa: E402
from production_stack_tpu.ops.int4_matmul import (  # noqa: E402
    int4_matmul,
    int4_matmul_stacked,
)
from production_stack_tpu.ops.mla_attention import mla_decode  # noqa: E402
from production_stack_tpu.ops.paged_attention_pallas import (  # noqa: E402
    pallas_paged_attention,
    pallas_paged_attention_decode_write,
)

H, KH, HD, BS, W = 32, 8, 128, 128, 256  # llama-3-8b layer, 32k table
LIVE = 20_000 + 37  # ragged final page on purpose
SCALE = 1.0 / np.sqrt(HD)
# Outputs are convex combinations of unit-normal V rows (|out| ~ 1e-1);
# bf16 output rounding alone is ~4e-3 relative. fp8 pages carry e4m3
# rounding of P on the kernel side only (split-precision PV dot).
ATTN_BOUND = 2e-2
# fp32 accumulation on both sides; the kernel contracts bf16 x with the exact
# nibbles and scales the fp32 partial by the fp32 scale, the reference
# multiplies by the fp32 dequant — outputs are O(1) sums of din terms.
INT4_BOUND = 5e-2
HBM_BYTES_S = 819e9  # one v5e chip (``perf/peaks.json``)

_Q_SLICE = 256


def _pages(rng, nb, dtype):
    x = rng.standard_normal((1, nb, 2, BS, KH * HD)).astype(np.float32)
    return jnp.asarray(x, jnp.bfloat16).astype(dtype)


def _tables(rng, B, nb):
    return jnp.asarray(
        (rng.permutation(nb - 1)[: B * W] + 1).reshape(B, W).astype(np.int32)
    )


def _reference(q, kv, tables, kv_lens, q_pos, window, softcap, scale=SCALE):
    """Gather attention over the live table prefix, in q-row slices."""
    live_w = -(-int(kv_lens.max()) // BS)
    ref = jax.jit(
        lambda q, kv, t, l, p: gather_paged_attention(
            q, kv, t, l, p, 0, scale=scale, window=window, softcap=softcap
        )
    )
    outs = [
        ref(q[:, s : s + _Q_SLICE], kv, tables[:, :live_w], kv_lens,
            q_pos[:, s : s + _Q_SLICE])
        for s in range(0, q.shape[1], _Q_SLICE)
    ]
    return np.asarray(jnp.concatenate(outs, axis=1), np.float32)


def attention_case(name, *, B, T, kv_dtype, window=0, softcap=0.0):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    nb = B * W + 2
    q = jnp.asarray(rng.standard_normal((B, T, H, HD)), jnp.bfloat16)
    kv = _pages(rng, nb, kv_dtype)
    tables = _tables(rng, B, nb)
    kv_lens = jnp.full((B,), LIVE, jnp.int32)
    q_pos = jnp.asarray(
        LIVE - T + np.tile(np.arange(T, dtype=np.int32), (B, 1))
    )
    kern = jax.jit(
        lambda q, kv, t, l, p: pallas_paged_attention(
            q, kv, t, l, p, 0, scale=SCALE, window=window, softcap=softcap
        )
    )
    t0 = time.perf_counter()
    got = np.asarray(kern(q, kv, tables, kv_lens, q_pos), np.float32)
    compile_s = time.perf_counter() - t0
    want = _reference(q, kv, tables, kv_lens, q_pos, window, softcap)
    return {
        "max_abs_diff": float(np.abs(got - want).max()),
        "ref_abs_max": float(np.abs(want).max()),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def cell_shape_case(name, *, B, kv_heads, kv_dtype, lo, hi, empty=(),
                    heads=H, paired=False, window=0, head_dim=HD):
    """Decode as a benchmark cell calls it: ragged lengths, a stacked cache
    read at a traced layer, NaN wherever the kernel must not look (with a
    ``window``, every page below it too: the cache manager has released
    those). ``paired``: the differential attention's queries, ``[q1 | 0]``
    and ``[0 | q2]`` by turns, at the scale of half a head."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    HD = head_dim
    lanes, layers, layer = kv_heads * HD, 3, 2
    scale = 1.0 / np.sqrt(HD // 2 if paired else HD)
    lens = rng.integers(lo, hi, B).astype(np.int32)
    lens[list(empty)] = 0
    width = -(-hi // BS) + 8
    nb = B * width + 2
    kv = rng.standard_normal((nb, 2, BS, lanes)).astype(np.float32)
    kv[1] = np.nan
    kv = jnp.asarray(kv, jnp.bfloat16).astype(kv_dtype)
    stack = jnp.stack([jnp.full_like(kv, np.nan)] * layer + [kv])
    tables = (rng.permutation(B * width) + 2).reshape(B, width)
    dead = np.arange(width)[None] >= -(-lens // BS)[:, None]
    if window:
        dead |= np.arange(width)[None] < (np.maximum(lens - window, 0) // BS)[:, None]
    q = rng.standard_normal((B, 1, heads, HD)).astype(np.float32)
    if paired:
        q[:, :, 0::2, HD // 2:] = 0.0
        q[:, :, 1::2, :HD // 2] = 0.0
    q = jnp.asarray(q, jnp.bfloat16)
    q_pos = jnp.asarray(lens - 1)[:, None]
    kern = jax.jit(
        lambda q, kv, t, l, p, ly: pallas_paged_attention(
            q, kv, t, l, p, ly, scale=scale, window=window)
    )
    t0 = time.perf_counter()
    got = np.asarray(kern(
        q, stack, jnp.asarray(np.where(dead, 1, tables).astype(np.int32)),
        jnp.asarray(lens), q_pos, jnp.int32(layer)), np.float32)
    compile_s = time.perf_counter() - t0
    # The reference multiplies every gathered V: give it page 0 for the dead.
    ref = jax.jit(
        lambda q, kv, t, l, p: gather_paged_attention(
            q, kv, t, l, p, 0, scale=scale, window=window)
    )
    want = np.asarray(ref(
        q, kv[None], jnp.asarray(np.where(dead, 0, tables).astype(np.int32)),
        jnp.asarray(lens), q_pos), np.float32)
    live = lens > 0
    return {
        "max_abs_diff": float(np.abs(got[live] - want[live]).max()),
        "empty_rows_max_abs_diff": float(np.abs(got[~live]).max(initial=0.0)),
        "ref_abs_max": float(np.abs(want[live]).max()),
        "kv_tokens": int(lens.sum()),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def shared_run_case(name, *, B, kv_heads, heads, kv_dtype, bs, shared, lo, hi,
                    empty=(), calls=64, T=1, window=0, key_floor=0, layers=3):
    """Decode behind one shared prompt, as the prefix cache leaves it:
    every live row's first ``shared`` tokens are the same physical pages,
    then ``lo`` to ``hi`` tokens of its own (``ops/paged_attention_pallas.py
    ::_find_shared_run``: the kernel reads the shared pages once a call). Checked
    like ``cell_shape_case`` (NaN wherever the kernel must not look), then
    timed: ``calls`` calls chained in one program over the layers of a
    stacked cache, against the least time the chip's memory allows for the
    rows' contexts (what a walk a row has to read) and for the distinct
    tokens among them (what this call has to). It runs on a tree without the
    phase too: copy it into that tree's ``scripts/``. ``T`` > 1 (PR 54): a
    short run of ``T`` query positions a row, the last ``T`` of its tokens
    (a verify-and-draft step; ``window`` / ``key_floor``: through a window
    layer, where every page below the window is NaN too, and through the
    draft layer), one row's last position padding."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    lanes = kv_heads * HD
    own = rng.integers(lo, hi, B).astype(np.int32)
    lens = shared + own
    lens[list(empty)] = 0
    n_shared = shared // bs
    width = -(-(shared + hi) // bs) + 8
    nb = B * width + n_shared + 2
    kv = rng.standard_normal((layers, nb, 2, bs, lanes)).astype(np.float32)
    kv[:, 1] = np.nan
    kv = jnp.asarray(kv, jnp.bfloat16).astype(kv_dtype)
    tables = (rng.permutation(B * width) + n_shared + 2).reshape(B, width)
    tables[:, :n_shared] = 2 + np.arange(n_shared)
    dead = np.arange(width)[None] >= -(-lens // bs)[:, None]
    q = jnp.asarray(rng.standard_normal((B, T, heads, HD)), jnp.bfloat16)
    first = np.maximum(lens - T, 0)
    if T > 1:
        first[0] += 1  # row 0's last position lies at ``kv_len``: padding
    q_pos = jnp.asarray(first[:, None] + np.arange(T)[None]).astype(jnp.int32)
    if window:
        dead |= np.arange(width)[None] < (
            np.maximum(first + 1 - window, 0) // bs)[:, None]
    t_kern = jnp.asarray(np.where(dead, 1, tables).astype(np.int32))
    lens_j = jnp.asarray(lens)
    how = dict(scale=SCALE, window=window, key_floor=key_floor)
    kern = jax.jit(
        lambda q, kv, t, l, p, ly: pallas_paged_attention(
            q, kv, t, l, p, ly, **how))
    t0 = time.perf_counter()
    got = np.asarray(kern(
        q, kv, t_kern, lens_j, q_pos, jnp.int32(layers - 1)), np.float32)
    compile_s = time.perf_counter() - t0
    ref = jax.jit(
        lambda q, kv, t, l, p: gather_paged_attention(
            q, kv, t, l, p, layers - 1, **how))
    want = np.asarray(ref(
        q, kv, jnp.asarray(np.where(dead, 0, tables).astype(np.int32)),
        lens_j, q_pos), np.float32)

    def chain(q, kv, t, l, p):
        def body(i, q):
            out = pallas_paged_attention(
                q, kv, t, l, p, jax.lax.rem(i, layers), **how)
            return q + out * 1e-3
        return jax.lax.fori_loop(0, calls, body, q)

    timed = jax.jit(chain)
    jax.block_until_ready(timed(q, kv, t_kern, lens_j, q_pos))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(timed(q, kv, t_kern, lens_j, q_pos))
        best = min(best, (time.perf_counter() - t0) / calls)
    rows_live = lens > 0
    live = (np.asarray(q_pos) < lens[:, None]) & rows_live[:, None]
    token_bytes = 2 * lanes * jnp.dtype(kv_dtype).itemsize
    read = np.minimum(lens, window + T - 1) if window else lens
    distinct = int(read.sum()) - (
        0 if window else (int(rows_live.sum()) - 1) * shared)
    return {
        "max_abs_diff": float(np.abs(got[live] - want[live]).max()),
        "empty_rows_max_abs_diff": float(np.abs(got[~live]).max(initial=0.0)),
        "ref_abs_max": float(np.abs(want[live]).max()),
        "kv_tokens": int(read.sum()),
        "distinct_tokens": distinct,
        "us_a_call": round(best * 1e6, 2),
        "roofline_us_rows": round(int(read.sum()) * token_bytes / HBM_BYTES_S * 1e6, 2),
        "roofline_us_distinct": round(distinct * token_bytes / HBM_BYTES_S * 1e6, 2),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def prefill_cell_case(name, *, T, real, start, kv_dtype, window=0,
                      softcap=0.0, heads=H, kv_heads=KH, head_dim=HD):
    """One prefill row as the runner pads it: ``real`` tokens of a
    ``T``-token bucket after ``start`` cached ones, a stacked cache read at
    a traced layer, NaN wherever the kernel must not look. The real rows
    against gather; the padding rows must come back as zeros."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    HD, scale = head_dim, 1.0 / np.sqrt(head_dim)
    layers, layer = 3, 2
    kv_len = start + real
    width = -(-(start + T) // BS) + 8
    nb = width + 2
    kv = rng.standard_normal((nb, 2, BS, kv_heads * HD)).astype(np.float32)
    kv[1] = np.nan
    kv = jnp.asarray(kv, jnp.bfloat16).astype(kv_dtype)
    stack = jnp.stack([jnp.full_like(kv, np.nan)] * layer + [kv])
    tables = (rng.permutation(width) + 2).reshape(1, width)
    first = max(start + 1 - window, 0) // BS if window else 0
    page = np.arange(width)[None]
    dead = (page >= -(-kv_len // BS)) | (page < first)
    q = jnp.asarray(rng.standard_normal((1, T, heads, HD)), jnp.bfloat16)
    # The runner's padding: every position past the chunk repeats its last.
    q_pos = jnp.asarray(
        np.minimum(start + np.arange(T), kv_len - 1)[None].astype(np.int32))
    lens = jnp.asarray([kv_len], jnp.int32)
    kern = jax.jit(
        lambda q, kv, t, l, p, ly: pallas_paged_attention(
            q, kv, t, l, p, ly, scale=scale, window=window, softcap=softcap)
    )
    t0 = time.perf_counter()
    got = np.asarray(kern(
        q, stack, jnp.asarray(np.where(dead, 1, tables).astype(np.int32)),
        lens, q_pos, jnp.int32(layer)), np.float32)
    compile_s = time.perf_counter() - t0
    want = _reference(
        q, kv[None], jnp.asarray(np.where(dead, 0, tables).astype(np.int32)),
        lens, q_pos, window, softcap, scale)
    return {
        "max_abs_diff": float(np.abs(got[:, :real] - want[:, :real]).max()),
        "padding_rows_max_abs_diff": float(
            np.abs(got[:, real:]).max(initial=0.0)),
        "ref_abs_max": float(np.abs(want[:, :real]).max()),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def scan_case(name, *, B, T, lens=None):
    """The Mamba-1 kernels (``ops/selective_scan.py``) at the published
    widths (16 states x 5,120 channels, nine layers, 75 slots), compiled,
    against the same recurrence in ``jax.numpy``: ``T == 1`` the decode
    kernel, else the prefill kernel on rows of true length ``lens``. The
    pool's other slots and layers must come back bit for bit; the second
    call is timed."""
    from production_stack_tpu.ops import selective_scan as ss

    rng = np.random.default_rng(zlib.crc32(name.encode()))
    L, S, N, Di, li = 9, 75, 16, 5120, 4
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32))
    pool = f(L, S, N, Di)
    u, bm, cm = f(B, T, Di), f(B, T, N), f(B, T, N)
    dt = 0.1 * jnp.abs(f(B, T, Di))
    a_t = -jnp.exp(0.5 * f(N, Di))
    d = jnp.ones((Di,), jnp.float32)
    slots = jnp.asarray(rng.permutation(S - 1)[:B].astype(np.int32))
    keep = jnp.asarray((np.arange(B) % 3 != 1).astype(np.int32))
    lens = jnp.asarray(lens if lens is not None else [T] * B, jnp.int32)
    valid = jnp.arange(T)[None, :] < lens[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    s0 = jnp.where(keep[:, None, None] != 0, pool[li, slots], 0.0)
    y_ref, s_ref = jax.jit(ss.scan_reference)(s0, u, dt, a_t, bm, cm, d)
    if T == 1:
        kern = jax.jit(lambda pool: ss.selective_scan_decode(
            pool, li, slots, keep, u[:, 0], dt[:, 0], a_t, bm[:, 0],
            cm[:, 0], d))
    else:
        kern = jax.jit(lambda pool: ss.selective_scan_prefill(
            pool, li, slots, keep, lens, u, dt, a_t, bm, cm, d))
    t0 = time.perf_counter()
    y, out = kern(pool)
    y = np.asarray(y, np.float32).reshape(B, T, Di)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(kern(pool))
    second_s = time.perf_counter() - t0
    live = np.asarray(valid)[..., None]
    others = np.setdiff1d(np.arange(S), np.asarray(slots))
    rows = np.asarray(lens) > 0
    return {
        "max_abs_diff": float(np.abs(np.where(live, y - np.asarray(y_ref), 0)).max()),
        "state_max_abs_diff": float(np.abs(
            np.asarray(out[li, slots]) - np.asarray(s_ref))[rows].max()),
        "ref_abs_max": float(np.abs(np.where(live, np.asarray(y_ref), 0)).max()),
        "exact": bool(
            np.array_equal(np.asarray(out[li, others]), np.asarray(pool[li, others]))
            and np.array_equal(np.asarray(out[li - 1]), np.asarray(pool[li - 1]))
            and np.isfinite(y).all()),
        "bound": 2e-2,
        "first_call_s": round(compile_s, 2),
        "second_call_ms": round(second_s * 1e3, 3),
    }


def delta_case(name, *, B, T, lens=None, keep=None):
    """The gated-delta-rule kernels (``ops/gated_delta.py``) at the
    published widths (32 heads of a 128 x 128 float32 state, twelve layers,
    74 slots), compiled, against the recurrence position by position in
    ``jax.numpy`` at "highest": ``T == 1`` the decode kernel, else the
    chunked prefill kernel on rows of true length ``lens`` (``keep`` 0: a
    row starts from zeros over whatever its slot holds). A token's decay
    spans 0.2-0.9999 as the configuration's does; the slots hold states of
    unit scale. The pool's other slots and layers must come back bit for
    bit; the second call is timed."""
    from production_stack_tpu.ops import gated_delta as gd

    rng = np.random.default_rng(zlib.crc32(name.encode()))
    L, S, Hd, K, V, li = 12, 74, 32, 128, 128, 5
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    pool = f(L, S, Hd, K, V)
    q, k, v = unit(f(B, T, Hd, K)) * K ** -0.5, unit(f(B, T, Hd, K)), f(B, T, Hd, V)
    g = -jnp.exp(jnp.asarray(rng.uniform(-9.0, 0.5, (B, T, Hd)), jnp.float32))
    beta = jax.nn.sigmoid(f(B, T, Hd))
    slots = jnp.asarray(rng.permutation(S - 1)[:B].astype(np.int32))
    keep = jnp.asarray(keep if keep is not None
                       else (np.arange(B) % 3 != 1).astype(np.int32))
    lens = jnp.asarray(lens if lens is not None else [T] * B, jnp.int32)
    valid = (jnp.arange(T)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    s0 = jnp.where(keep[:, None, None, None] != 0, pool[li, slots], 0.0)
    o_ref, s_ref = jax.jit(gd.delta_reference)(s0, q, k, v, g, beta)
    if T == 1:
        kern = jax.jit(lambda pool: gd.gated_delta_decode(
            pool, li, slots, keep, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            beta[:, 0]))
    else:
        kern = jax.jit(lambda pool: gd.gated_delta_prefill(
            pool, li, slots, keep, lens, q, k, v, g, beta))
    t0 = time.perf_counter()
    o, out = kern(pool)
    o = np.asarray(o, np.float32).reshape(B, T, Hd, V)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(kern(pool))
    second_s = time.perf_counter() - t0
    live = np.asarray(valid)[..., None]
    others = np.setdiff1d(np.arange(S), np.asarray(slots))
    rows = np.asarray(lens) > 0
    return {
        "max_abs_diff": float(np.abs(np.where(live, o - np.asarray(o_ref), 0)).max()),
        "state_max_abs_diff": float(np.abs(
            np.asarray(out[li, slots]) - np.asarray(s_ref))[rows].max()),
        "ref_abs_max": float(np.abs(np.where(live, np.asarray(o_ref), 0)).max()),
        "state_abs_max": float(np.abs(np.asarray(s_ref)).max()),
        "exact": bool(
            np.array_equal(np.asarray(out[li, others]), np.asarray(pool[li, others]))
            and np.array_equal(np.asarray(out[li - 1]), np.asarray(pool[li - 1]))
            and np.isfinite(o[np.broadcast_to(live, o.shape)]).all()),
        # float32 on both sides, the kernel's products at "highest": the
        # chunked form orders its sums differently and nothing else
        "bound": 2e-4,
        "first_call_s": round(compile_s, 2),
        "second_call_ms": round(second_s * 1e3, 3),
    }


def conv_tail_case(name, *, B):
    """``conv_tail_decode`` (``ops/gated_delta.py``) at the gated-delta
    cell's widths (twelve layers, 73 slots, a tail of 3 x 8,192 bf16, four
    taps), compiled, against the ``jax.numpy`` gather, shift and scatter it
    replaced in the decode step: ``B`` rows of which three are padding at
    the scratch slot and a third start from zeros. Real rows' tails and
    every slot and layer the step does not own must come back bit for bit,
    the sums to float32 rounding; the second call is timed."""
    from production_stack_tpu.ops import gated_delta as gd

    rng = np.random.default_rng(zlib.crc32(name.encode()))
    L, S, taps, C, li = 12, 73, 4, 8192, 5
    bf = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32), jnp.bfloat16)
    pool = bf(L, S, *gd.tail_shape(taps, C))
    x, w = bf(B, C), bf(taps, C)
    real = np.arange(B) < B - 3
    slots = jnp.asarray(
        np.where(real, rng.permutation(S - 1)[:B], S - 1).astype(np.int32))
    keep = jnp.asarray(((np.arange(B) % 3 != 1) & real).astype(np.int32))
    lens = jnp.asarray(real.astype(np.int32))
    want, pool_ref = jax.jit(gd.conv_tail_reference)(
        pool, li, slots, keep, lens, x[:, None], w)
    kern = jax.jit(lambda pool: gd.conv_tail_decode(pool, li, slots, keep, x, w))
    t0 = time.perf_counter()
    got, out = kern(pool)
    got = np.asarray(got, np.float32)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(kern(pool))
    second_s = time.perf_counter() - t0
    others = np.setdiff1d(np.arange(S), np.asarray(slots))
    settled = np.arange(S - 1)  # every slot but the scratch
    return {
        "max_abs_diff": float(np.abs(got - np.asarray(want)[:, 0])[real].max()),
        "ref_abs_max": float(np.abs(np.asarray(want)[:, 0][real]).max()),
        "exact": bool(
            np.array_equal(out[li, settled], pool_ref[li, settled])
            and np.array_equal(out[li, others], pool[li, others])
            and np.array_equal(out[li - 1], pool[li - 1])
            and np.array_equal(out[li + 1], pool[li + 1])
            and np.isfinite(got).all()),
        # four float32 products summed in the same order on both sides
        "bound": 1e-5,
        "first_call_s": round(compile_s, 2),
        "second_call_ms": round(second_s * 1e3, 3),
    }


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")
# what may carry a pool: the program's plumbing, and a gather's or a
# scatter's in-place forms in a prefill step (the pool their operand)
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "call",
             "conditional", "bitcast"}
_IN_PLACE = {"fusion", "scatter", "dynamic-update-slice"}


def pool_instructions(text, pool, allowed):
    """-> (calls of ``conv_tail_decode`` with ``pool`` in their result, the
    other instructions of ``text`` with it there whose opcode is not
    ``allowed`` or that place it in fast memory)."""
    found, kernels = [], 0
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or pool not in m.group(2):
            continue
        if "conv_tail_decode" in line and m.group(3) == "custom-call":
            kernels += 1
        elif m.group(3) not in allowed:
            found.append(f"{m.group(1)} {m.group(3)}")
        if any("S(1)" in t for t in re.findall(
                re.escape(pool) + r"\S*", m.group(2))):
            found.append(f"{m.group(1)} in S(1)")
    return kernels, found


def tails_program_case(name, *, B, T):
    """The gated-delta cell's step program (``Qwen3Next.forward`` at
    ``perf/configs/qwen3-next-ep8-cut.json``, ``B`` rows of ``T`` positions,
    the cache donated), compiled, read as text: which instructions have the
    tails' pool in their result. A decode step (``T == 1``): the program's
    plumbing and ``conv_tail_decode``'s own call, nothing else, and the pool
    nowhere in fast memory (``S(1)``). A prefill step keeps the ``jax.numpy``
    gather and scatter, so a scatter over the pool in place may stand there;
    a copy of it (``copy``, ``copy-start``, ``copy-done``) or ``S(1)`` may
    not, in either."""
    from production_stack_tpu.models import qwen3_next as qn

    with open(os.path.join(ROOT, "perf", "configs", "qwen3-next-ep8-cut.json")) as f:
        cfg = qn.config_from_hf(json.load(f), "qwen3-next-ep8-cut")
    model = qn.Qwen3Next(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: model.make_kv_cache(2048, 128, None, state_slots=72))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def step(params, tokens, positions, write_idx, tables, kv_lens, last_idx,
             cache, slots):
        return model.forward(
            params, tokens, positions, write_idx, tables, kv_lens, last_idx,
            cache, state_slots=slots, token_budget=1024 if T > 1 else None,
            attn_impl="pallas")

    t0 = time.perf_counter()
    text = jax.jit(step, donate_argnums=(7,)).lower(
        params, i32(B, T), i32(B, T), i32(B, T), i32(B, 128), i32(B), i32(B),
        cache, i32(B)).compile().as_text()
    compile_s = time.perf_counter() - t0
    pool = "bf16[" + ",".join(map(str, cache["conv"].shape)) + "]"
    kernels, found = pool_instructions(
        text, pool, _PLUMBING | (_IN_PLACE if T > 1 else set()))
    return {
        "pool": pool,
        "kernel_calls_in_text": kernels,
        "other_instructions_of_the_pools_shape": found[:20],
        "exact": not found and (kernels > 0) == (T == 1),
        "bound": 0.0,
        "compile_s": round(compile_s, 2),
    }


def mla_case(name, *, lens):
    """``mla_decode`` as the latent cell calls it: 20 heads, rank 512 + 64
    rotary lanes in rows of 640, a stacked cache read at a traced layer,
    NaN wherever the kernel must not look."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    heads, rank, rope, lanes, layers, layer = 20, 512, 64, 640, 3, 2
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    width = -(-int(lens.max()) // BS) + 8
    nb = B * width + 2
    kv = np.zeros((nb, 1, BS, lanes), np.float32)
    kv[..., :rank + rope] = rng.standard_normal((nb, 1, BS, rank + rope))
    kv[1] = np.nan
    kv = jnp.asarray(kv, jnp.bfloat16)
    stack = jnp.stack([jnp.full_like(kv, np.nan)] * layer + [kv])
    tables = (rng.permutation(B * width) + 2).reshape(B, width)
    dead = np.arange(width)[None] >= -(-lens // BS)[:, None]
    q = jnp.asarray(rng.standard_normal((B, heads, rank + rope)), jnp.bfloat16)
    scale = 1.0 / 16.0
    kern = jax.jit(lambda q, kv, t, l, ly: mla_decode(
        q, kv, t, l, ly, rank=rank, scale=scale))
    t0 = time.perf_counter()
    got = np.asarray(kern(
        q, stack, jnp.asarray(np.where(dead, 1, tables).astype(np.int32)),
        jnp.asarray(lens), jnp.int32(layer)), np.float32)
    compile_s = time.perf_counter() - t0

    @jax.jit
    def ref(q, rows, n):  # one sequence: gather, mask, softmax, weigh
        s = jnp.einsum("hc,sc->hs", q.astype(jnp.float32),
                       rows[:, :rank + rope].astype(jnp.float32),
                       precision="highest") * scale
        s = jnp.where(jnp.arange(rows.shape[0])[None] < n, s, -jnp.inf)
        return jnp.einsum("hs,sc->hc", jax.nn.softmax(s, -1),
                          rows[:, :rank].astype(jnp.float32),
                          precision="highest")

    worst, ref_max = 0.0, 0.0
    for b in np.nonzero(lens)[0]:
        pages = np.where(dead[b], 0, tables[b])
        want = np.asarray(ref(q[b], kv[pages, 0].reshape(-1, lanes), lens[b]))
        worst = max(worst, float(np.abs(got[b] - want).max()))
        ref_max = max(ref_max, float(np.abs(want).max()))
    return {
        "max_abs_diff": worst,
        "empty_rows_max_abs_diff": float(np.abs(got[lens == 0]).max(initial=0.0)),
        "ref_abs_max": ref_max,
        "kv_tokens": int(lens.sum()),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def fused_write_case(name, *, kv_dtype, ragged=False):
    """Decode step with the KV write folded into the kernel vs XLA scatter
    then gather attention; also compares the rows the kernel wrote."""
    B = 8
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    nb = B * W + 2
    q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.bfloat16)
    kv = _pages(rng, nb, kv_dtype)
    tables = _tables(rng, B, nb)
    lens = [1, 700, 1024, 1025, 3000, 130, LIVE, 300] if ragged else [LIVE] * B
    kv_lens = jnp.asarray(lens, jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((B, KH * HD)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((B, KH * HD)), jnp.bfloat16)
    pos = kv_lens - 1
    blk = tables[jnp.arange(B), pos // BS]
    write_flat = (blk * BS + pos % BS).astype(jnp.int32)

    def scatter(kv):
        kv = kv.at[0, blk, 0, pos % BS].set(k_new.astype(kv.dtype))
        return kv.at[0, blk, 1, pos % BS].set(v_new.astype(kv.dtype))

    kv_ref = jax.jit(scatter)(kv)
    q_pos = pos[:, None]
    want = _reference(q[:, None], kv_ref, tables, kv_lens, q_pos, 0, 0.0)[:, 0]
    want_rows = np.asarray(
        kv_ref[0, blk, :, pos % BS].astype(jnp.float32))

    kern = jax.jit(
        lambda q, kv, t, l, k, v, wf: pallas_paged_attention_decode_write(
            q, kv, t, l, 0, k, v, wf, scale=SCALE
        ),
        donate_argnums=(1,),
    )
    t0 = time.perf_counter()
    out, kv_out = kern(q, kv, tables, kv_lens, k_new, v_new, write_flat)
    got = np.asarray(out, np.float32)
    compile_s = time.perf_counter() - t0
    got_rows = np.asarray(kv_out[0, blk, :, pos % BS].astype(jnp.float32))
    return {
        "max_abs_diff": float(np.abs(got - want).max()),
        "written_rows_max_abs_diff": float(np.abs(got_rows - want_rows).max()),
        "ref_abs_max": float(np.abs(want).max()),
        "bound": ATTN_BOUND,
        "first_call_s": round(compile_s, 2),
    }


def int4_case(name, *, rows, din, dout, layers=0, li=0):
    """``layers`` = 0: the 2-D entry on one matrix. Otherwise the stacked
    entry on ``layers`` different matrices, asked for layer ``li``."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    w = jnp.asarray(
        rng.standard_normal((max(layers, 1), din, dout)) / np.sqrt(din),
        jnp.bfloat16,
    )
    packed3, scales3 = jax.jit(quantize_leaf_int4)(w)
    packed, scales = packed3[li], scales3[li]
    x = jnp.asarray(rng.standard_normal((rows, din)), jnp.bfloat16)
    t0 = time.perf_counter()
    if layers:
        got = np.asarray(
            int4_matmul_stacked(x, packed3, scales3, jnp.int32(li)), np.float32
        )
    else:
        got = np.asarray(int4_matmul(x, packed, scales), np.float32)
    compile_s = time.perf_counter() - t0
    want = np.asarray(
        jax.jit(
            lambda x, p, s: jnp.dot(
                x.astype(jnp.float32), dequant_int4(p, s, jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
        )(x, packed, scales)
    )
    res = {
        "max_abs_diff": float(np.abs(got - want).max()),
        "ref_abs_max": float(np.abs(want).max()),
        "bound": INT4_BOUND,
        "first_call_s": round(compile_s, 2),
    }
    if layers:
        res["exact"] = bool(np.array_equal(
            got, np.asarray(int4_matmul(x, packed, scales), np.float32)
        ))
    return res


def cases():
    fp8 = jnp.float8_e4m3fn
    for tag, dt in (("bf16", jnp.bfloat16), ("fp8", fp8)):
        yield f"attn_decode_b8_{tag}", attention_case, dict(
            B=8, T=1, kv_dtype=dt)
        yield f"attn_prefill_t1024_{tag}", attention_case, dict(
            B=1, T=1024, kv_dtype=dt)
        yield f"attn_decode_b8_{tag}_window_softcap", attention_case, dict(
            B=8, T=1, kv_dtype=dt, window=4096, softcap=50.0)
        yield f"attn_prefill_t1024_{tag}_window_softcap", attention_case, dict(
            B=1, T=1024, kv_dtype=dt, window=4096, softcap=50.0)
        yield f"attn_decode_fused_write_{tag}", fused_write_case, dict(
            kv_dtype=dt)
    yield "attn_decode_fused_write_fp8_ragged", fused_write_case, dict(
        kv_dtype=fp8, ragged=True)
    yield "attn_decode_cell_dense_b16_kh8_fp8", cell_shape_case, dict(
        B=16, kv_heads=8, kv_dtype=fp8, lo=3000, hi=11000, empty=(5, 15))
    yield "attn_decode_cell_hybrid_b32_kh2_bf16", cell_shape_case, dict(
        B=32, kv_heads=2, kv_dtype=jnp.bfloat16, lo=1000, hi=2500)
    # Rows behind one prompt (PR 50): 16 graders behind 512 few-shot tokens
    # on the looped cell's 32-token bf16 pages of 16 KV heads, and 16
    # sessions behind a 1,024-token system prompt on the dense cell's fp8
    # pages, one row finished.
    yield "attn_decode_shared_ouro_b16_kh16_bf16", shared_run_case, dict(
        B=16, kv_heads=16, heads=16, kv_dtype=jnp.bfloat16, bs=32,
        shared=512, lo=64, hi=256)
    yield "attn_decode_shared_dense_b16_kh8_fp8", shared_run_case, dict(
        B=16, kv_heads=8, heads=32, kv_dtype=fp8, bs=128, shared=1024,
        lo=2000, hi=8000, empty=(11,))
    # A verify-and-draft step (PR 54): 64 thinkers behind 1,024 shared tokens
    # on the draft cell's bf16 pages of 8 KV heads, two query positions a row
    # through the decode stream (``paged_attn_short``): the full layer, a
    # window layer, the draft layer under its key floor; and a dense prefill
    # bucket of four positions on fp8 pages.
    short = dict(B=64, kv_heads=8, heads=64, kv_dtype=jnp.bfloat16, bs=128,
                 shared=1024, lo=1300, hi=4000, empty=(11,), T=2, layers=1)
    yield "attn_short_exaone_b64_t2_kh8_bf16", shared_run_case, short
    yield "attn_short_exaone_b64_t2_window128", shared_run_case, dict(
        short, window=128)
    yield "attn_short_exaone_b64_t2_key_floor", shared_run_case, dict(
        short, key_floor=1)
    yield "attn_short_dense_b8_t4_kh8_fp8", shared_run_case, dict(
        B=8, kv_heads=8, heads=32, kv_dtype=fp8, bs=128, shared=1024,
        lo=2000, hi=8000, T=4, layers=1)
    yield "attn_prefill_cell_dense_t256_real150_fp8", prefill_cell_case, dict(
        T=256, real=150, start=8192 - 37, kv_dtype=fp8)
    yield "attn_prefill_t1024_real600_fp8_window_softcap", prefill_cell_case, dict(
        T=1024, real=600, start=4096 + 71, kv_dtype=fp8, window=2048,
        softcap=50.0)
    # The decoder-hybrid-decoder's cell: 40 query heads of [q1 | 0] / [0 | q2]
    # over 10 key-value heads of pairs at 1.5-7.2k of context (32 of the
    # cell's 64 rows: the case's stacked cache of NaN layers has to fit); the
    # window layers with every page below the 512 window released.
    phi = dict(kv_heads=10, heads=40, kv_dtype=jnp.bfloat16)
    yield "attn_decode_cell_phi_b32_kh10_paired", cell_shape_case, dict(
        B=32, lo=1500, hi=7200, paired=True, **phi)
    yield "attn_decode_cell_phi_b32_kh10_paired_window512", cell_shape_case, dict(
        B=32, lo=1500, hi=7200, paired=True, window=512, empty=(7,), **phi)
    yield "attn_prefill_cell_phi_t1024_real700_window512", prefill_cell_case, dict(
        T=1024, real=700, start=2048 + 71, window=512, **phi)
    yield "attn_prefill_cell_phi_t1024_real1024_full", prefill_cell_case, dict(
        T=1024, real=1024, start=2048, **phi)
    yield "scan_decode_b64", scan_case, dict(B=64, T=1)
    yield "scan_prefill_b1_t1024", scan_case, dict(B=1, T=1024)
    yield "scan_prefill_b4_t256_ragged", scan_case, dict(
        B=4, T=256, lens=[256, 131, 5, 0])
    # The gated-delta-rule hybrid's cell: 16 query heads over 2 key-value
    # heads of 256 lanes at 0.3-5k of context (32 of the cell's 64 rows: the
    # case's stacked cache of NaN layers has to fit), and its two kernels.
    wide = dict(kv_heads=2, heads=16, head_dim=256, kv_dtype=jnp.bfloat16)
    yield "attn_decode_cell_qwen3next_b32_kh2_hd256", cell_shape_case, dict(
        B=32, lo=300, hi=5000, empty=(7,), **wide)
    yield "attn_prefill_cell_qwen3next_t1024_real700_hd256", prefill_cell_case, dict(
        T=1024, real=700, start=1024 + 71, **wide)
    yield "attn_prefill_cell_qwen3next_t1024_real1024_hd256", prefill_cell_case, dict(
        T=1024, real=1024, start=0, **wide)
    yield "delta_decode_b64", delta_case, dict(B=64, T=1)
    yield "delta_prefill_b1_t1024", delta_case, dict(B=1, T=1024, keep=[0])
    yield "delta_prefill_b4_t256_ragged", delta_case, dict(
        B=4, T=256, lens=[256, 131, 5, 0])
    yield "delta_prefill_b1_t1024_continued", delta_case, dict(
        B=1, T=1024, lens=[777], keep=[1])
    yield "qwen3next_conv_tail_decode_b64", conv_tail_case, dict(B=64)
    yield "qwen3next_program_tails_b64_t1", tails_program_case, dict(B=64, T=1)
    yield "qwen3next_program_tails_b1_t1024", tails_program_case, dict(
        B=1, T=1024)
    yield "qwen3next_program_tails_b4_t256", tails_program_case, dict(
        B=4, T=256)
    docs = np.exp(np.linspace(np.log(16384), np.log(40960), 12)).astype(int)
    yield "mla_decode_cell_b16_bf16", mla_case, dict(
        lens=[0, *docs[:6], 0, 0, *(docs[6:] + 137), 0])
    yield "mla_decode_ragged_b16_bf16", mla_case, dict(
        lens=[1, 127, 128, 129, 0, 511, 512, 513, 2047, 2048, 2049, 2100,
              4096, 4609, 6145, 0])
    for rows in (8, 1024):
        for din, dout in ((4096, 14336), (14336, 4096)):
            yield f"int4_matmul_n{rows}_{din}x{dout}", int4_case, dict(
                rows=rows, din=din, dout=dout)
    stacked = [(rows, din, dout) for rows in (16, 256, 64, 128)
               for din, dout in ((4096, 14336), (14336, 4096))]
    stacked += [(16, 4096, 4096), (16, 4096, 1024)]
    for rows, din, dout in stacked:
        yield f"int4_matmul_stacked_n{rows}_{din}x{dout}", int4_case, dict(
            rows=rows, din=din, dout=dout, layers=3, li=2)


def main() -> int:
    if resolve_platform() != "tpu":
        print("tpu_kernel_check: backend is not tpu — this check exists to "
              "meet the Mosaic compiler; the interpreted kernels are covered "
              "by tests/", file=sys.stderr)
        return 2
    report = {
        "device": describe_devices(),
        "memory_stats": jax.local_devices()[0].memory_stats(),
        "cases": {},
    }
    print(json.dumps({k: report[k] for k in ("device", "memory_stats")}),
          flush=True)
    failed = []
    only = sys.argv[1:]  # substrings of case names; none = every case
    for name, fn, kw in cases():
        if only and not any(o in name for o in only):
            continue
        try:
            res = fn(name, **kw)
            diffs = [v for k, v in res.items() if k.endswith("max_abs_diff")]
            res["ok"] = res.get("exact", True) and all(
                np.isfinite(d) and d <= res["bound"] for d in diffs
            )
        except Exception as e:  # noqa: BLE001 — report every kernel, then fail
            res = {
                "ok": False,
                "error": f"{type(e).__name__}: {e}"[:4000],
                "traceback": traceback.format_exc()[-6000:],
            }
        report["cases"][name] = res
        if not res["ok"]:
            failed.append(name)
        shown = {k: v for k, v in res.items() if k != "traceback"}
        print(f"{name}: {json.dumps(shown)}", flush=True)
    report["ok"] = not failed
    report["failed"] = failed
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kernel_check.json", "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": report["ok"], "failed": failed,
                      "device": report["device"]}), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
