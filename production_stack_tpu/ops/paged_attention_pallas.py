"""Pallas TPU flash kernels over paged KV: decode and chunked prefill.

The hot ops of the serving loop (the role vLLM's CUDA PagedAttention +
flash-attn kernels play behind the reference stack). The two are bound by
different things and each is organized around its own bound:

- **Decode is bound by its copies.** One query row a sequence against 3-11k
  cached tokens is HBM bandwidth and nothing else, so that kernel is
  organized around DMA efficiency, not grid geometry: it runs within 2 % of
  its bare page copies (PERF.md §6, PR 32), and its ``p @ V`` keeps fp8
  pages as they are (``_pv_dot``: V is the large operand there). So is a
  **short run** of query positions a row (a verify step's two, a prefill
  bucket of 2 or 4: ``rides_stream``): up to 128 query lines fold under
  the same copies, so it takes the same stream with a bound a line
  (``paged_attn_short``; PERF.md §6, PR 54).
- **Prefill is bound by its fold.** A chunk of 128-256 query positions x 4
  grouped heads is 512-1,024 rows a KV head: a 1 MiB chunk is copied in
  1.3 us and folded in tens, so what a call costs is what the fold does per
  element of its ``[rows, 512]`` probability tile (``_chunked_flash``;
  PERF.md §6, PR 34): pages are widened to bf16 once a chunk rather than the
  tile rounded to e4m3 and back (the tile is the large operand here), and
  query rows past a row's real length are not folded.

Both stream pages the same way (``_page_dma_loop``):

- KV lives in one combined page array ``[nb, 2, bs, KH*hd]`` (a page holds
  its K rows then V rows, each token row spanning **all** kv heads in the
  lane dimension), so one async copy moves an entire page — 100s of KB per
  DMA instead of the 8 KB per-head fragments a ``[KH, nb, bs, hd]`` layout
  forces. The head fold keeps the minor dims at ``(bs, KH*hd)``: both
  tiling-exact, no sublane padding (a ``[..., KH, hd]`` tail would pad
  KH=8 → 16 sublanes and physically double the cache).
- The grid is tiny — ``(B,)`` for decode, ``(B, T/Tq)`` for prefill — and
  each cell walks its sequence's **live** pages in chunks of ``C`` pages
  through a small ring of buffer slots (``_page_dma_loop``), the next
  chunks' DMAs in flight while the oldest chunk's flash accumulation
  runs. A page moves only if it holds a token some query of the cell may
  see: pages past ``kv_len``, pages below a sliding window, for prefill
  pages entirely above the tile's causal horizon, and the dead pages of a
  row's ragged first and last chunk are never fetched at all (the round-2
  kernel's ``pl.when`` skipped the *compute* but the BlockSpec pipeline
  still paid the *DMA*; that was the round-2 TTFT regression). The fold
  masks by column; ``_zero_values`` says why buffer rows no copy of the
  chunk wrote are harmless.
- Both grids are sequential, and within a decode call the stream does not
  stop at a row's end: while a cell folds its row's last chunks, the
  first chunks of the rows after it are already in flight, so per layer
  call the DMA engine runs from the first row's first live page to the
  last row's last with one exposed fetch at the start and one exposed
  fold at the end.
- Flash state (m/l/acc) is head-major in VMEM scratch so per-head slices are
  contiguous; grouped-query heads share each page read.
- Rows of a decode call behind one prompt hold the same leading pages (a
  prefix-cache hit). Those are streamed once a call, ahead of the rows' own
  pages in the same queue, and folded against every row's query at once
  (``_find_shared_run``, ``_decode_kernel``'s shared phase; PERF.md §6,
  PR 50).

Scalar-prefetched block tables address the pages (``PrefetchScalarGridSpec``)
so page ids are in SMEM before the body runs.

The kernels take the FULL stacked cache ``[L, nb, 2, bs, KH*hd]`` plus a
(possibly traced) layer index rather than a per-layer slice: inside the
model's layer scan a slice would materialize the whole 100s-of-MB layer
cache as a copy per layer per step, while the ANY-space operand costs
nothing — the DMA engine reads only the pages the sequence actually needs.

Shapes:
  q           [B, T, H, hd]        T=1 decode, T=chunk prefill; a short
                                   chunk (``rides_stream``) takes decode's
                                   stream
  kv_pages    [L, nb, 2, bs, KH*hd] combined K(row 0)/V(row 1) pages
  tables      [B, W] int32         page ids (W*bs >= kv_len)
  kv_lens     [B] int32            valid KV length per sequence (0 = padding)
  q_positions [B, T] int32         absolute position per query token; a
                                   chunk's kernel (prefill, a short run)
                                   uses row 0 (chunks are consecutive
                                   positions, of which those at and past
                                   kv_len are padding and return zeros —
                                   runner contract); the one position of
                                   decode is kv_len - 1
  layer       int32 scalar         layer to read (scalar-prefetched)
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import pallas_interpret
from .attention import window_eff

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# Two ``p @ V`` for two shapes whose needs conflict. In decode the
# probabilities are ``[32, S]`` and V ``[S, 1024]`` is the large operand:
# widening the streamed V costs more than the product, so ``_pv_dot`` splits
# the probabilities in fp8 instead. In prefill the sides are reversed: the
# probability tile has ``rows >= 2 x head_dim`` times a chunk's span and
# the round trip over it was most of a call, so ``_chunked_flash`` hands
# ``_pv_dot`` a V already widened to bf16 (``_fold_dtype`` chooses by the
# shapes) and the split below is not taken.
def _pv_dot(p, v):
    """probs @ V with fp32 accumulation, correct for quantized caches.

    With an fp8 cache, casting probs to e4m3 for the dot quantizes the
    softmax weights themselves to ~2 significant digits (caught by the
    model-level numerics oracle) — but converting the STREAMED V chunks up
    to bf16 costs a per-chunk relayout that measured 6x slower end to end.
    Instead: split-precision in fp8. The main dot uses e4m3-rounded probs;
    a second dot carries the 16x-scaled rounding residual (≤ p/16, so the
    scale re-centers it in e4m3's mantissa range). Effective probs
    precision ~2^-8 — bf16-equivalent — while V never leaves its 1-byte
    layout and the PV MXU cost (a small slice of a DMA-bound kernel)
    merely doubles."""
    if jnp.dtype(v.dtype).itemsize != 1:
        return jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    p8 = p.astype(v.dtype)
    resid = ((p - p8.astype(jnp.float32)) * 16.0).astype(v.dtype)
    main = jax.lax.dot_general(
        p8, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    fix = jax.lax.dot_general(
        resid, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return main + fix * 0.0625


# The decode stream's geometry, measured standalone on a v5e at the two
# shapes the benchmark runs (``scripts/tpu_decode_attn_attrib.py``; PERF.md
# §6, PR 32: 16 rows x 8 KV heads of fp8 at 3-11k, and 32 rows x 2 KV
# heads of bf16 at 1-2.5k; share of the HBM roofline):
#   chunk 1,024 tokens, 2 slots, whole-chunk fold   82 %   72 %
#   chunk 1,024 tokens, 3 slots, fold by 512        86 %   82 %
#   chunk 2,048 tokens, 3 slots, whole-chunk fold   85 %   83 %
#   chunk 2,048 tokens, 3 slots, fold by 512        89 %   84 %
# against 90.6 % for the same copies with nothing folded. Why each: a
# chunk's fold costs 0.43 us plus 2.0 us per 1,024 tokens against 2.8 us
# per 1,024 tokens of copies, so bigger chunks keep the fold under the
# copies; a third slot keeps a full chunk queued behind a row's short last
# one (with two, the DMA engine idles through half a fold at every row's
# end); and the fold's time goes with the columns it is given, so a last
# chunk is folded only up to its last live page, in steps of 512 tokens.
# A fourth slot, 256-token steps and 512-token chunks gave nothing or lost.
_DECODE_CHUNK_TOKENS = 2048
_DECODE_FOLD_TOKENS = 512
_DECODE_SLOTS = 3
# The ring may take half of the call's 64 MiB of VMEM: pages of many KV
# heads in two bytes get fewer pages a chunk (fp8 x 8 heads: 12 MiB).
_DECODE_RING_BYTES = 32 * 1024 * 1024
# Prefill is bound by its fold, not by its copies (3 % of a call), so its
# chunk is sized for the fold: a sub-tile's row state (running maximum, sum,
# the rescaled accumulator: 256 rows x 1 lane in 32 vregs each) is updated
# once a chunk and costs 0.66 us, twice what a ``[256, 512]`` tile's two
# products do, so longer chunks halve it; at 2,048 the dead columns of a
# context's ragged last chunk, which are widened and folded like live ones,
# cost more than that saves (standalone on a v5e, a 256-token bucket over
# 5.4k / 7.0k cached tokens, us a call on the revision that still widened
# with ``astype``: 512: 402 / 507; 1,024: 346 / 399; 2,048: 347 / 449;
# ``scripts/tpu_prefill_attn_attrib.py``; PERF.md §6, PR 34). The ring
# keeps two slots.
_PREFILL_CHUNK_TOKENS = 1024
# The ring's two slots and the fold's own copy of a chunk share this much
# VMEM: pages of many KV heads in two bytes get fewer pages a chunk.
_PREFILL_KV_BYTES = 16 * 1024 * 1024
# Rows of one head a prefill sub-tile folds at a time (64 positions of a
# group of four query heads): enough rows to keep the MXU full, and a
# quarter of a 256-position bucket, so a bucket's padding is skipped in
# quarters (128 rows: 511 us where 256 take 402; 512: 454; 1,024: 657).
_PREFILL_SUB_ROWS = 256

# A short run's shared phase folds a KV head's ``[rows x group, columns]``
# scores (float32) in tiles of at most this much: 64 rows x 16 lines x 512
# columns. Mosaic unrolls a body over its tile's registers, and a kernel's
# short cells run slower the more code it holds, **executed or not**: in the
# K-EXAONE step a window call, which never enters the phase, took 358 us with
# the phase's tile at 4 MiB, 251 at 2 MiB and 229 with no phase traced
# (PERF.md §6, PR 54; alone in a loop all three read 230). One position
# keeps PR 50's whole-view fold: every cell's ``paged_attn_decode`` is the
# program it was.
_SHARED_TILE_BYTES = 2 * 1024 * 1024

# Copies a chunk may hold: each is a descriptor, a semaphore and two
# branches unrolled into the loop's body (pages far smaller than the 128
# tokens the deployments run would otherwise unroll hundreds).
_MAX_CHUNK_PAGES = 32


def _chunk_pages(bs: int, target_tokens: int) -> int:
    """Pages per DMA buffer slot (~target_tokens per chunk)."""
    return min(max(target_tokens // bs, 1), _MAX_CHUNK_PAGES)


class _LiveRange(NamedTuple):
    """What one grid cell streams of one table row, all traced scalars:
    pages ``[first_page, n_pages)`` in chunks ``[c_start, n_chunks)``. The
    cell that starts a chunk's copies and the cell that waits for them
    build their descriptors from the same range (``_decode_range``)."""

    row: Any  # place in the stream (``across_rows`` walks these in order)
    table: Any  # row of the block table its page ids stand in
    first_page: Any  # first page a query may see (sliding window; else 0)
    n_pages: Any  # pages at or past this hold no live token
    c_start: Any  # first chunk with a live page
    n_chunks: Any  # exclusive end


def _zero_values(buf):
    """Zero the V half of every buffer slot (a call's first grid cell does
    this once; scratch persists across a sequential grid). A chunk's dead
    pages are not copied, so their buffer rows hold what an earlier chunk
    left: finite cache bytes whose columns get ``p`` exactly 0. VMEM that
    no copy of this call has written yet may hold a NaN pattern, and
    ``0 x NaN`` in ``_pv_dot`` is NaN. K needs no guard: its scores are
    replaced by a select."""
    zero = jnp.zeros(buf.shape[3:], buf.dtype)
    for slot in range(buf.shape[0]):
        for j in range(buf.shape[1]):
            buf[slot, j, 1] = zero


# Words of the stream's state, carried from cell to cell in SMEM.
_STREAM_STATE_WORDS = 5


def _page_dma_loop(
    *,
    live: _LiveRange,  # this cell's row
    layer,  # int32 layer index into the stacked cache
    tables_ref,  # [B, W] SMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY
    buf,  # [K, C, 2, bs, KH*hd] VMEM scratch: a ring of K chunk slots
    sems,  # [K, C] DMA semaphores
    chunk: int,
    compute_chunk,  # (view of pages [n, 2, bs, KH*hd], first position) -> None
    fold_pages: int = 0,  # fold a ragged last chunk in steps of this many
    across_rows=None,  # (state_ref SMEM, row -> _LiveRange, rows in grid)
):
    """Page streaming shared by decode and prefill: a ring of K chunk
    slots, up to K-1 chunks' copies in flight while ``compute_chunk``
    folds the oldest (K = 2 is double buffering).

    Only live pages move: a copy is issued, and later waited for, where
    its page lies in ``[first_page, n_pages)``. Chunks below ``c_start``
    (entirely outside a sliding window) are neither fetched nor folded;
    the dead pages of a row's first and last chunk are not fetched, the
    fold masks their columns, and ``_zero_values`` is why that is safe.

    The stream is a queue of chunks in the order the cells fold them. An
    *issuer* (next row and chunk to start, next slot to fill) runs ahead
    of the *folder* (oldest slot) by as many chunks as there are free
    slots. Without ``across_rows`` the issuer stops at this row's end:
    each cell warms up and drains alone. With it (a sequential grid; plain
    decode) the issuer walks on into the next row, so the copies never
    pause at a row's end and a short last chunk is followed at once by the
    next row's first. It stops at the grid's end and before a row with
    nothing live (decode padding): that row's cell starts and waits for
    nothing, and its successor, like cell 0, finds nothing in flight and
    points the issuer at its own first chunk. Issuer and folder build a
    chunk's descriptors from the same ``_LiveRange``, and slots are filled
    and drained in the same order, so each wait meets the copy it names."""
    C, K, bs = chunk, buf.shape[0], buf.shape[3]

    def copies(r: _LiveRange, c, slot, op: str):
        # One descriptor a live page of chunk c: a loop, not C unrolled
        # branches, so a step program traces and lowers one of them.
        def one(j, _):
            getattr(pltpu.make_async_copy(
                kv_hbm.at[layer, tables_ref[r.table, c * C + j]],
                buf.at[slot, j], sems.at[slot, j],
            ), op)()
            return 0

        jax.lax.fori_loop(
            jnp.maximum(r.first_page - c * C, 0),
            jnp.minimum(r.n_pages - c * C, C), one, 0,
        )

    def issue(st, limit):
        """Start the issuer's chunk if fewer than ``limit`` are in flight,
        and move the issuer on."""
        row, c, head, tail, inflight = st
        go = (row >= 0) & (inflight < limit)
        if across_rows is None:
            r, after, after_c = live, -1, 0
        else:
            _, range_of, n_rows = across_rows
            r = range_of(jnp.maximum(row, 0))
            nxt = range_of(jnp.minimum(r.row + 1, n_rows - 1))
            walk_on = (r.row + 1 < n_rows) & (nxt.n_chunks > nxt.c_start)
            after, after_c = jnp.where(walk_on, r.row + 1, -1), nxt.c_start

        @pl.when(go)
        def _():
            copies(r, c, head, "start")

        more = c + 1 < r.n_chunks
        return (
            jnp.where(go, jnp.where(more, row, after), row),
            jnp.where(go, jnp.where(more, c + 1, after_c), c),
            jnp.where(go, jax.lax.rem(head + 1, K), head),
            tail,
            inflight + go.astype(jnp.int32),
        )

    has = live.n_chunks > live.c_start
    if across_rows is None:
        st = (jnp.where(has, live.row, -1), live.c_start, 0, 0, 0)
    else:
        state = across_rows[0]
        st = tuple(state[i] for i in range(_STREAM_STATE_WORDS))
        # Nothing in flight for a live row: cell 0, or the cell after a
        # padding row. The issuer starts here.
        cold = has & (st[4] == 0)
        st = (jnp.where(cold, live.row, st[0]),
              jnp.where(cold, live.c_start, st[1])) + st[2:]
    for _ in range(K - 1):
        st = issue(st, K - 1)

    def body(c, st):
        row, ic, head, tail, inflight = issue(st, K)
        copies(live, c, tail, "wait")
        if not fold_pages:
            compute_chunk(buf.at[tail], c * C * bs)
        else:
            # The fold's time goes with the columns it is given, live or
            # masked: hand a chunk over only from its first live page to
            # its last, in whole steps of ``fold_pages`` (one body a size).
            # Its first page is 0 unless a window or a shared run
            # (``_decode_range``) starts the row inside the chunk.
            top = jnp.minimum(live.n_pages - c * C, C)
            if isinstance(live.first_page, int) and live.first_page == 0:
                low = 0
            else:
                low = jnp.maximum(live.first_page - c * C, 0)
                low = low - jax.lax.rem(low, fold_pages)
            for n in range(fold_pages, C + 1, fold_pages):
                @pl.when((top - low > n - fold_pages) & (top - low <= n))
                def _(n=n):
                    compute_chunk(
                        buf.at[tail, pl.ds(low, n)], (c * C + low) * bs)
        return row, ic, head, jax.lax.rem(tail + 1, K), inflight - 1

    st = jax.lax.fori_loop(live.c_start, live.n_chunks, body, st)
    if across_rows is not None:
        for i in range(_STREAM_STATE_WORDS):
            state[i] = st[i]


def _real_positions(kv_len, first, q_tile: int):
    """Query positions of a tile that hold a token. The runner pads a
    chunk to a power of two and says how long the row really is:
    ``kv_len`` is the chunk's last token + 1 (``engine/runner.py::
    _prefill_batch``), so the positions at and past it are padding."""
    return jnp.clip(kv_len - first, 0, q_tile)


def _fold_dtype(kv_dtype, rows: int, head_dim: int):
    """The dtype a chunk's K and V are folded in. e4m3 pages are widened to
    bf16 once a chunk (exact: every e4m3 value is a bf16 value) where the
    probability tile (``rows x span``) has at least the elements of the
    chunk's K and V slices (``2 x span x head_dim``): then widening the
    pages is the cheap side and ``_pv_dot``'s split over the probabilities
    the dear one. A handful of query rows (a speculative verify step) is
    decode's case and keeps the pages as they are."""
    if kv_dtype == jnp.float8_e4m3fn and rows >= 2 * head_dim:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(kv_dtype)


def _widen_e4m3(x8):
    """``[S, n]`` e4m3 -> the two halves of ``[S, n]`` bf16, exactly, **rows
    permuted** (``_widened_rows``). The compiler's own conversion unpacks
    and repacks sublanes and took 12.9 us for 2 x 512 x 1,024 values, more
    than the MXU needs to fold them; on the packed words it is 4.9 us
    (PERF.md §6, PR 34). A 32-bit word of the input holds rows ``4r..4r+3``
    of one lane, a byte each. Each byte is moved to where a float32 keeps
    exponent and mantissa (``e + 120``; a subnormal as ``2^-6 (1 + m/8) -
    2^-6``, which is why this goes through float32 at all), and the top
    halves of two such words are one word of bf16. Which two rows share a
    word is free, because attention sums over a chunk's tokens in any order
    as long as K, V and the mask agree on it: bytes 0 and 1 make the first
    half of the result, bytes 2 and 3 the second, and no value changes lane
    or sublane. The two NaN bytes come out as 480; no live page holds one
    (``_zero_values`` on what else a buffer may)."""
    # ``lax`` primitives, not ``jnp`` operators: each operator is a nested
    # ``jit`` to trace, and a step program's first use in a process pays for
    # this body's trace whether or not its compilation is cached.
    lax = jax.lax
    w = pltpu.bitcast(x8, jnp.int32)  # [S/4, n]
    # The four bytes of every word at once: [4, S/4, n], byte k on top.
    top = jnp.stack([lax.shift_left(w, 24 - 8 * k) for k in range(3)] + [w])
    mag = lax.shift_right_logical(lax.bitwise_and(top, 0x7F000000), 4)
    sub = lax.lt(mag, 8 << 20)  # exponent field 0
    f = lax.sub(
        pltpu.bitcast(
            lax.add(mag, lax.select(sub, lax.full_like(mag, 121 << 23),
                                    lax.full_like(mag, 120 << 23))),
            jnp.float32,
        ),
        lax.select(sub, lax.full_like(mag, 2.0**-6, jnp.float32),
                   lax.full_like(mag, 0.0, jnp.float32)),
    )
    bits = lax.bitwise_or(
        pltpu.bitcast(f, jnp.int32), lax.bitwise_and(top, -(1 << 31)))

    def pair(lo, hi):  # rows (4r + k_lo, 4r + k_hi) -> rows (2r, 2r + 1)
        return pltpu.bitcast(
            lax.bitwise_or(
                lax.shift_right_logical(lax.index_in_dim(bits, lo, keepdims=False), 16),
                lax.bitwise_and(lax.index_in_dim(bits, hi, keepdims=False), -(1 << 16)),
            ),
            jnp.bfloat16,
        )

    return pair(0, 1), pair(2, 3)


def _widened_rows(S: int):
    """``[1, S]``: the chunk row that ``_widen_e4m3`` leaves at each row of
    its result."""
    i = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    j = jax.lax.rem(i, S // 2)
    return 4 * (j // 2) + 2 * (i // (S // 2)) + jax.lax.rem(j, 2)


def _chunked_flash(
    *,
    live, layer, tables_ref, kv_hbm, buf, sems,
    q_s,  # [KH, n_sub, Rs, hd] query rows, head-major (native dtype)
    kv_s,  # [2, S, KH*hd] the chunk's K and V in the fold's dtype
    m_ref,  # [KH, n_sub, Rs, 128] fp32 scratch (col 0 live)
    l_ref,  # [KH, n_sub, Rs, 128]
    acc_ref,  # [KH, n_sub, Rs, hd]
    n_sub,  # sub-tiles that hold a real query row (traced)
    first,  # the tile's first query position
    kv_len,
    win_eff,
    scale: float,
    block_size: int,
    chunk: int,
    group: int,
    head_dim: int,
    softcap: float = 0.0,
    key_floor: int = 0,
):
    """Per-head flash accumulation over streamed KV chunks, the prefill
    shape: many query rows a head, so the fold and not the stream is what
    a call waits for (PERF.md §6, PR 34: the copies are 3 % of a call).

    - The query tile is folded in sub-tiles of ``Rs`` rows
      (``_PREFILL_SUB_ROWS``); the ``n_sub`` that hold a real row are
      folded, the padding behind them is not.
    - A chunk's K and V are laid out ``[S, KH*hd]`` once, e4m3 pages
      widened to bf16 (``_fold_dtype``, ``_widen_e4m3``), so each head's
      scores and its ``p @ V`` are one MXU product each in the operands' own
      dtype with fp32 accumulation: bf16 probabilities over fp8 pages, what
      decode's split product reaches in two; exact for the fp32 oracle
      tests. With that the products set a sub-tile's pace and the masks
      hide under them (measured: folding interior chunks without compares
      and selects won 0.2 %), so every chunk is folded by the one masked
      body."""
    hd, G = head_dim, group
    KH, _, Rs, _ = acc_ref.shape
    S = chunk * block_size
    widened = kv_s.dtype != buf.dtype

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(h, j, col):
        lanes = pl.ds(pl.multiple_of(h * hd, hd), hd)  # head h's lanes
        s = jax.lax.dot_general(
            q_s[h, j], kv_s[0, :, lanes], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Rs, S] fp32
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        rows = jax.lax.broadcasted_iota(jnp.int32, (Rs, 1), 0)
        q_pos = first + j * (Rs // G) + rows // G  # rows t*G+g: position t
        seen = (col < jnp.minimum(q_pos + 1, kv_len)) & (
            col >= q_pos + 1 - win_eff
        )
        if key_floor:  # static: a layer stored one slot ahead, slot 0 empty
            seen &= col >= key_floor
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[h, j, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h, j, :, :1] = alpha * l_ref[h, j, :, :1] + jnp.sum(
            p, axis=-1, keepdims=True
        )
        m_ref[h, j, :, :1] = m_new
        acc_ref[h, j] = acc_ref[h, j] * alpha + _pv_dot(p, kv_s[1, :, lanes])

    def compute(page, col0):
        page = page[...]
        for i in range(2):  # K, V
            x = page[:, i].reshape(S, KH * hd)
            if widened:
                kv_s[i, : S // 2], kv_s[i, S // 2 :] = _widen_e4m3(x)
            else:
                kv_s[i] = x
        # The position each of the chunk's columns stands for.
        col = col0 + (
            _widened_rows(S) if widened
            else jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        )

        # One body for every head and sub-tile: a step program traces and
        # lowers it once (its first use in a process pays for that, cache
        # or no cache).
        def head_sub_tile(i, _):
            fold(i // n_sub, jax.lax.rem(i, n_sub), col)
            return 0

        jax.lax.fori_loop(0, KH * n_sub, head_sub_tile, 0)

    _page_dma_loop(
        live=live, layer=layer, tables_ref=tables_ref, kv_hbm=kv_hbm,
        buf=buf, sems=sems, chunk=chunk, compute_chunk=compute,
    )


def _decode_range(lens_ref, win_ref, row, *, span: int, bs: int, skip=0,
                  starts_ref=None, positions: int = 1):
    """The live range of decode row ``row``, its length and the first
    position its walk folds. The one query row sits at position kv_len-1
    and may see positions >= kv_len - window (0 = unlimited); whole chunks
    below that are never fetched, nor the pages below it in the chunk it
    starts in. ``skip``: leading pages the call's shared phase has folded
    for this row already (``_find_shared_run``; 0 under a window). A short
    run of ``positions`` queries from ``starts_ref[row]`` on
    (``rides_stream``) walks the union of their views: from the first
    one's window start to the last one's horizon."""
    kv_len = end = above = lens_ref[row]  # above: the first query's horizon
    if starts_ref is not None:
        above = starts_ref[row] + 1
        end = jnp.minimum(starts_ref[row] + positions, kv_len)
    lo = jnp.maximum(above - window_eff(win_ref[0]), skip * bs)
    return kv_len, lo, _LiveRange(
        row=row, table=row, first_page=lo // bs,
        n_pages=(end + bs - 1) // bs,
        c_start=lo // span, n_chunks=(end + span - 1) // span,
    )


def _find_shared_run(tables_ref, lens_ref, rows: int, bs: int,
                     starts_ref=None):
    """(pages, first live row), traced scalars: the leading pages every live
    row of a decode call holds in common, and the row whose table names them.

    A prefix-cache hit hands rows the same physical pages, so the run is a
    comparison of page ids, a column of the table at a time until one
    differs: nothing is hashed. Rows with ``kv_len`` 0 (padding, a finished
    member of a chain) are left out; every live row keeps the pages from
    its first query position on (``starts_ref``; None: the one query at
    ``kv_len - 1``), those it writes, to itself; a call with one live row
    shares nothing. Found by the call's first cell on the scalar core, from the
    tables and lengths it has in SMEM anyway (a few hundred scalar
    operations, under a microsecond): as XLA operations of the step program
    the search's tail was sunk into the layer scan and cost six small
    fusions a layer (PERF.md §6, PR 50). ``engine/runner.py::
    shared_prefix_run`` is its twin on the host, which only counts."""
    i32 = jnp.int32

    def look(i, found):
        first, live, cap = found
        kv_len = lens_ref[i]
        here = kv_len > 0

        def below():  # the last position under the row's first query
            # (a closure: traced where the one query's ``kv_len - 1`` was)
            if starts_ref is None:
                return kv_len - 1
            return jnp.minimum(starts_ref[i], kv_len - 1)

        return (jnp.where(here & (first < 0), i, first),
                live + here.astype(i32),
                jnp.where(here, jnp.minimum(cap, below() // bs), cap))

    first, live, cap = jax.lax.fori_loop(
        0, rows, look, (i32(-1), i32(0), i32(1 << 30)))
    first = jnp.maximum(first, 0)
    cap = jnp.where(live > 1, cap, 0)

    def column_shared(j):
        page = tables_ref[first, j]
        return jax.lax.fori_loop(
            0, rows,
            lambda i, same: same & (
                (lens_ref[i] == 0) | (tables_ref[i, j] == page)).astype(i32),
            i32(1))

    def step(at):
        same = column_shared(at[0])
        return at[0] + same, same

    pages, _ = jax.lax.while_loop(
        lambda at: (at[1] > 0) & (at[0] < cap), step, (i32(0), i32(1)))
    return pages, first


def decode_shares(rows: int, heads: int, head_dim: int, window=0):
    """Does a decode call of ``rows`` rows run the shared phase? THE rule:
    the call's trace, its first cell and the engine's count of what the
    phase spares (``ops/attention.py::decode_sharing_calls``) all ask here.
    ``heads``: the query lines a row (a short run's ``positions x heads``).
    More than one row; on the chip rows and heads in whole sublane tiles
    of float32 and heads in whole lines of 128 lanes (the phase keeps its
    state head-major in slabs of ``rows`` rows and each row's cell takes
    its own with one strided load); and no window, which bounds a row's
    reads already and may lie above its shared pages. ``window`` may be
    the layer's traced scalar: then so is the answer."""
    if rows < 2 or not (bool(pallas_interpret()) or (
            rows % 8 == 0 and heads % 8 == 0 and head_dim % 128 == 0)):
        return False
    return window <= 0


# Query lines one block-diagonal fold takes: the MXU's rows on the one chip
# the program knows (``device.py::DEVICE_TABLE``).
_STREAM_LINES = 128


def rides_stream(positions: int, heads: int) -> bool:
    """Does a call of ``positions`` query positions a row go through the
    decode stream (``_decode_kernel``)? THE rule, by shape alone: one
    position always (``paged_attn_decode``); a short run whose ``positions x
    heads`` query lines one block-diagonal fold takes (``paged_attn_short``:
    a verify step's two or few positions a row, a prefill bucket of 2 or 4)
    too, which is bound by its copies as decode is; anything longer is bound
    by its fold and is the chunk kernel's (``paged_attn_prefill``)."""
    return positions == 1 or positions * heads <= _STREAM_LINES


class _SharedPhase(NamedTuple):
    """What ``_decode_call`` hands a kernel that traces the shared phase
    (``H``: the query lines a row, ``positions x heads``)."""

    q_all: Any  # [B, H, hd] VMEM: every row's query whole
    run: Any  # SMEM (pages of the run, first live row)
    # VMEM float32, a line a (head, row), heads in blocks of 128 lanes:
    q32: Any  # the queries row-major
    q_sh: Any  # the queries head-major
    m_sh: Any  # [B*H, 128]
    l_sh: Any  # [B*H, 128]
    acc_sh: Any


def _decode_kernel(
    tables_ref, lens_ref, layer_ref, win_ref,  # scalar prefetch (SMEM)
    q_ref,  # [1, H, hd] VMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY
    o_ref,  # [1, H, hd] VMEM
    buf, sems, state, m_ref, l_ref, acc_ref,  # scratch (m/l [H,128], acc [H,hd])
    *,
    scale: float,
    block_size: int,
    chunk: int,
    fold_pages: int,
    group: int,
    head_dim: int,
    softcap: float = 0.0,
    prefetch_next_row: bool = True,
    phase: "_SharedPhase | None" = None,
    starts_ref=None,  # [B] SMEM: a short run's first query position a row
    positions: int = 1,
    key_floor: int = 0,
):
    """Dense folded-q decode: per-head [G, hd] x [hd, S] mat-vecs waste the
    MXU (G of 128 rows live) and burn VPU on per-head slices, so instead q
    is scattered block-diagonally into the page's lane layout —
    ``q_sparse[r]`` holds row r's head at lane block r//G, zeros elsewhere —
    and ONE [H, KH*hd] x [KH*hd, S] matmul per chunk yields every head's
    scores (cross-head lanes contribute exact zeros). The p@V product runs
    dense the same way; each row's own head block is extracted from
    [H, KH, hd] with the same mask. ~KH x more MACs, all on otherwise-idle
    MXU rows; the VPU flash update shrinks from KH G-row passes to one
    full-vreg [H, S] pass.

    The grid is sequential, so buffers, semaphores and ``state`` carry
    from cell to cell: while cell ``b`` folds its last chunks, the first
    chunks of the rows after it are already in flight
    (``_page_dma_loop``). ``prefetch_next_row`` is false where a write
    precedes the read (``_decode_write_kernel``): row ``b+1``'s first
    chunk may hold the page it has yet to write.

    **The shared phase** (``phase``). Rows behind one system
    prompt hold the same leading pages (``_find_shared_run``), and a walk a row
    would read them once a row. Instead the first cell streams them through
    the ring once, ahead of row 0's own pages and in the same queue, and
    folds each chunk against every row's query at once: a KV head at a
    time, its ``B x G`` query rows against that head's lanes of the chunk
    (the block-diagonal trick would multiply the work by ``KH`` here, where
    the rows are many enough to fill the MXU without it). The partial flash
    state stays head-major, ``[(head, row), ...]``; each row's cell takes
    its ``H`` lines of it with one strided load, starts its walk at the end
    of the run (``_decode_range``'s ``skip``) and finishes as ever: the
    shared pages are folded first either way, with the same products and
    the same rounding of ``p`` (``_pv_dot``). With a run of 0, under a
    window, the phase is one scalar comparison.

    **A short run** (``positions`` > 1: ``rides_stream``). A row's few
    consecutive query positions from ``starts_ref[b]`` on arrive as
    ``positions x heads`` lines laid out ``[KH, positions, G]``, that is as a
    row of that many heads in groups of ``positions x G`` (``group`` here),
    and everything above holds for them as it stands: the ring, the
    block-diagonal products, the hand-over, the shared phase (whose run ends
    below every row's *first* position). What differs is the mask, a bound a
    line where one ``kv_len`` served the row: a line of position ``t`` sees
    the columns below ``min(start + t + 1, kv_len)`` and from its own window
    bound on, and a position at or past ``kv_len`` is padding and returns
    zeros: the chunk kernel's contract (``pallas_paged_attention``).
    ``key_floor`` (static) masks the columns below it in both phases."""
    share = phase is not None
    if share:
        q_all_ref, run, q32, q_sh, m_sh, l_sh, acc_sh = phase
    b = pl.program_id(0)
    B = pl.num_programs(0)
    G, hd = group, head_dim
    H = q_ref.shape[1]
    KH = H // G
    span = chunk * block_size
    skip = 0
    if share:
        n_b = q_all_ref.shape[0]  # rows: static, as ``B`` is not

        @pl.when(b == 0)
        def _find_run():
            run[0] = 0
            run[1] = 0

            @pl.when(decode_shares(n_b, H, hd, win_ref[0]))
            def _():
                run[0], run[1] = _find_shared_run(
                    tables_ref, lens_ref, n_b, block_size, starts_ref)

        skip = run[0]  # pages of the shared run, for every cell of the call
    rng = functools.partial(
        _decode_range, lens_ref, win_ref, span=span, bs=block_size, skip=skip,
        starts_ref=starts_ref, positions=positions,
    )
    kv_len, lo, live = rng(b)

    def place(v):
        """The stream's places: the shared run, then the rows."""
        row = rng(jnp.maximum(v - 1, 0))[2]
        shared = v == 0
        return _LiveRange(
            row=v, table=jnp.where(shared, run[1], row.table),
            first_page=jnp.where(shared, 0, row.first_page),
            n_pages=jnp.where(shared, skip, row.n_pages),
            c_start=jnp.where(shared, 0, row.c_start),
            n_chunks=jnp.where(
                shared, (skip + chunk - 1) // chunk, row.n_chunks),
        )

    across_rows = None
    if share:
        live = place(b + 1)
        across_rows = (state, place, B + 1)
    elif prefetch_next_row:
        across_rows = (state, lambda row: rng(row)[2], B)
    stream = dict(
        layer=layer_ref[0], tables_ref=tables_ref, kv_hbm=kv_hbm, buf=buf,
        sems=sems, chunk=chunk, fold_pages=fold_pages, across_rows=across_rows,
    )

    @pl.when(b == 0)
    def _first_cell():
        _zero_values(buf)
        state[0] = -1  # the issuer points nowhere: this cell starts cold
        for i in range(1, _STREAM_STATE_WORDS):
            state[i] = 0

    if share:
        GB = G * n_b  # query rows a KV head
        # A strided load reads lines of 128 lanes: wider heads lie in blocks.
        n_l, lb = q_sh.shape[0], q_sh.shape[2]
        blocks = [(j, slice(j * lb, (j + 1) * lb)) for j in range(n_l)]

        @pl.when((b == 0) & (skip > 0))
        def _shared_phase():
            # Queries head-major: line ``r * B + row`` holds row ``row``'s
            # head ``r``. Through float32: a strided load wants whole words.
            def widen(i, _):
                for j, lanes in blocks:
                    q32[j, pl.ds(pl.multiple_of(i * H, H), H), :] = (
                        q_all_ref[i][:, lanes].astype(jnp.float32))
                return 0

            jax.lax.fori_loop(0, n_b, widen, 0)

            def turn(r, _):
                for j, _ in blocks:
                    q_sh[j, pl.ds(pl.multiple_of(r * n_b, n_b), n_b), :] = (
                        q32[j, pl.ds(r, n_b, stride=H), :])
                return 0

            jax.lax.fori_loop(0, H, turn, 0)
            m_sh[...] = jnp.full_like(m_sh, _NEG_INF)
            l_sh[...] = jnp.zeros_like(l_sh)
            acc_sh[...] = jnp.zeros_like(acc_sh)

            def fold_shared(page, col0):
                # A short run's ``[B*G, S]`` scores in tiles of at most
                # ``_SHARED_TILE_BYTES``: whole pages, a divisor of the view.
                n = page.shape[0]
                sub = n if positions == 1 else max(
                    d for d in range(1, n + 1) if n % d == 0 and (
                        d == 1
                        or d * block_size * GB * 4 <= _SHARED_TILE_BYTES))
                S = sub * block_size
                if sub == n:
                    return fold_columns(page, col0, S)

                def tile(i, _):
                    at = i * sub
                    fold_columns(page.at[pl.ds(at, sub)],
                                 col0 + at * block_size, S)
                    return 0

                jax.lax.fori_loop(0, n // sub, tile, 0)

            def fold_columns(page, col0, S):
                col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
                seen = col < skip * block_size
                if key_floor:
                    seen &= col >= key_floor

                def head(h, _):
                    lanes = pl.ds(pl.multiple_of(h * hd, hd), hd)
                    rows = pl.ds(pl.multiple_of(h * GB, GB), GB)
                    k = page[:, 0, :, lanes].reshape(S, hd)
                    v = page[:, 1, :, lanes].reshape(S, hd)
                    q_h = jnp.concatenate(
                        [q_sh[j, rows, :] for j, _ in blocks], axis=-1)
                    s = jax.lax.dot_general(
                        q_h.astype(q_ref.dtype), k,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale  # [B*G, S] fp32
                    if softcap:
                        s = jnp.tanh(s / softcap) * softcap
                    s = jnp.where(seen, s, _NEG_INF)
                    m_prev = m_sh[rows, :1]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=-1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    l_sh[rows, :1] = alpha * l_sh[rows, :1] + jnp.sum(
                        p, axis=-1, keepdims=True)
                    m_sh[rows, :1] = m_new
                    pv = _pv_dot(p, v)
                    for j, lanes in blocks:
                        acc_sh[j, rows, :] = (
                            acc_sh[j, rows, :] * alpha + pv[:, lanes])
                    return 0

                jax.lax.fori_loop(0, KH, head, 0)

            _page_dma_loop(
                live=place(0), compute_chunk=fold_shared, **stream)

    q = q_ref[0]  # [H, hd] native dtype
    # Arithmetic 0/1 mask (born 3D): Mosaic cannot minor-dim-reshape or
    # relayout sub-32-bit (bool) vectors, so the block-diagonal selector is
    # built as floats and applied by multiplication.
    row_head = jax.lax.broadcasted_iota(jnp.int32, (H, KH, 1), 0) // G
    head_idx = jax.lax.broadcasted_iota(jnp.int32, (H, KH, 1), 1)
    blockdiag = (row_head == head_idx).astype(jnp.float32)  # [H, KH, 1]
    q_sparse = (
        q[:, None, :] * blockdiag.astype(q.dtype)
    ).reshape(H, KH * hd)

    def fresh():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if share:
        # A live row goes on from what the shared phase left for it.
        took = (skip > 0) & (kv_len > 0)
        pl.when(~took)(fresh)

        @pl.when(took)
        def _take():
            mine = pl.ds(b, H, stride=n_b)  # head r's line of row b
            m_ref[...] = m_sh[mine, :]
            l_ref[...] = l_sh[mine, :]
            for j, lanes in blocks:
                acc_ref[:, lanes] = acc_sh[j, mine, :]
    else:
        fresh()

    # The columns a line sees, ``[low, high)``: scalars for the one query of
    # a row, ``[H, 1]`` for a short run, whose line ``r`` holds position
    # ``start + (r // G') % positions`` (``G'``: the heads a KV head).
    low, high, real = lo, kv_len, None
    if starts_ref is not None:
        line = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
        q_pos = starts_ref[b] + jax.lax.rem(line // (G // positions), positions)
        high = jnp.minimum(q_pos + 1, kv_len)
        low = jnp.maximum(
            q_pos + 1 - window_eff(win_ref[0]), skip * block_size)
        real = q_pos < kv_len
    if key_floor:
        low = jnp.maximum(low, key_floor)

    def compute(page, col0):
        page = page[...]
        S = page.shape[0] * block_size
        k = page[:, 0].reshape(S, KH * hd)
        v = page[:, 1].reshape(S, KH * hd)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        s = jax.lax.dot_general(
            q_sparse, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, S] fp32
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where((col >= low) & (col < high), s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = _pv_dot(p, v).reshape(H, KH, hd)
        own = (pv * blockdiag).sum(axis=1)  # each row's own head block
        acc_ref[...] = acc_ref[...] * alpha + own

    _page_dma_loop(live=live, compute_chunk=compute, **stream)
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)  # [H, hd]
    if real is not None:
        out = jnp.where(real, out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _decode_write_kernel(
    tables_ref, lens_ref, layer_ref, win_ref, wf_ref,  # scalar prefetch
    q_ref,  # [1, H, hd] VMEM
    k_ref,  # [1, 1, KH*hd] VMEM — this step's K row for this sequence
    v_ref,  # [1, 1, KH*hd] VMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY (aliased with kv_out)
    o_ref,  # [1, H, hd] VMEM
    kv_out,  # [L, nb, 2, bs, KH*hd] ANY — the SAME buffer (in-place)
    buf, sems, state, wbuf, wsems, m_ref, l_ref, acc_ref,
    **kw,
):
    """Decode step with the KV write folded in: each grid cell pulls its
    write page into VMEM, splices the new K/V row in with a masked select
    (sub-row DMA into a tiled fp8 page is not expressible — HBM slices
    must be tiling-aligned), pushes the page back, waits, then runs the
    standard flash read loop — the row just written is the newest position
    and is read back in the final chunk. Folding removes the per-layer
    XLA scatter from the decode step (a fixed ~0.2 ms x layers of pure op
    overhead on a 10 GiB carried buffer); the page round trip is ~512 KB
    per sequence per layer, noise next to the KV stream.

    Each cell warms up its own first chunk (``prefetch_next_row`` false):
    a chunk started by the cell before would read this row's write page
    before the row has written it."""
    b = pl.program_id(0)
    bs = kv_hbm.shape[3]
    nb = kv_hbm.shape[1]
    wf = wf_ref[b]
    ly = layer_ref[0]

    @pl.when(wf < nb * bs)
    def _write():
        blk = wf // bs
        pos = wf % bs
        pull = pltpu.make_async_copy(
            kv_out.at[ly, blk], wbuf, wsems.at[0]
        )
        pull.start()
        pull.wait()
        row = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        mask = row == pos
        page_k = jnp.where(
            mask, k_ref[0].astype(jnp.float32), wbuf[0].astype(jnp.float32)
        ).astype(wbuf.dtype)
        page_v = jnp.where(
            mask, v_ref[0].astype(jnp.float32), wbuf[1].astype(jnp.float32)
        ).astype(wbuf.dtype)
        wbuf[0] = page_k
        wbuf[1] = page_v
        push = pltpu.make_async_copy(
            wbuf, kv_out.at[ly, blk], wsems.at[1]
        )
        push.start()
        push.wait()

    # The per-row walk, no shared phase: a row's shared pages may hold the
    # page a row before it has yet to write.
    _decode_kernel(
        tables_ref, lens_ref, layer_ref, win_ref,
        q_ref, kv_out, o_ref, buf, sems, state, m_ref, l_ref, acc_ref,
        prefetch_next_row=False, **kw,
    )


def pallas_paged_attention_decode_write(
    q3: jax.Array,  # [B, H, hd]
    kv_pages: jax.Array,  # [L, nb, 2, bs, KH*hd] (donated by the caller)
    block_tables: jax.Array,  # [B, W]
    kv_lens: jax.Array,  # [B] valid length INCLUDING the row being written
    layer,  # int32 scalar
    k_new: jax.Array,  # [B, KH*hd]
    v_new: jax.Array,  # [B, KH*hd]
    write_flat: jax.Array,  # [B] flat slot blk*bs+pos; >= nb*bs drops
    *,
    scale: float,
    window=0,
    softcap: float = 0.0,
) -> "tuple[jax.Array, jax.Array]":
    """Fused write+attend decode step. Returns (out [B, H, hd], cache).
    The cache is updated IN PLACE (input/output aliased)."""
    B, H, hd, bs, lanes, C, kw, scratch, flash = _decode_geometry(
        q3, kv_pages, scale=scale, softcap=softcap
    )
    nb = kv_pages.shape[1]
    tables = block_tables.astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    wf = write_flat.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, t, l, ly, w, f: (b, 0, 0)),
            # [B, 1, lanes] with a singleton sublane dim: a (1, lanes)
            # trailing block is only legal when the sublane block equals
            # the array dim.
            pl.BlockSpec((1, 1, lanes), lambda b, t, l, ly, w, f: (b, 0, 0)),
            pl.BlockSpec((1, 1, lanes), lambda b, t, l, ly, w, f: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, hd), lambda b, t, l, ly, w, f: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=scratch + [
            pltpu.VMEM((2, bs, lanes), kv_pages.dtype),  # write page
            pltpu.SemaphoreType.DMA((2,)),
        ] + flash,
    )
    kernel = functools.partial(_decode_write_kernel, **kw)
    out, cache = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q3.dtype),
            jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        ],
        # Operand index 8 = kv_pages (after 5 scalar-prefetch args and
        # q/k/v); aliased onto output 1 so the 10 GiB cache updates in
        # place instead of copying.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=pallas_interpret(),
        name="paged_attn_decode_write",
    )(tables, lens, layer_arr, win_arr, wf,
      q3,
      k_new.astype(kv_pages.dtype)[:, None],
      v_new.astype(kv_pages.dtype)[:, None],
      kv_pages)
    return out, cache


def _prefill_kernel(
    tables_ref, lens_ref, starts_ref, layer_ref, win_ref,  # scalar prefetch
    q_ref,  # [1, Tq, H, hd] VMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY
    o_ref,  # [1, Tq, H, hd] VMEM
    buf, sems, q_s, kv_s, m_ref, l_ref, acc_ref,  # scratch
    *,
    scale: float,
    block_size: int,
    chunk: int,
    group: int,
    head_dim: int,
    q_tile: int,
    softcap: float = 0.0,
    key_floor: int = 0,
):
    b = pl.program_id(0)
    tq = pl.program_id(1)
    G, Tq = group, q_tile
    KH, n_sub, Rs, _ = acc_ref.shape
    kv_len = lens_ref[b]

    # Rows t*G+g of each head cover absolute positions first + t, of which
    # the first ``real`` hold a token. The tile's causal horizon is its
    # last real row's position; pages past it are never fetched (≈ halves
    # page traffic over a full prefill, while warm tiles near the sequence
    # end still stream every live page — exactly the data they need).
    first = starts_ref[b] + tq * Tq
    real = _real_positions(kv_len, first, Tq)
    limit = first + real
    span = chunk * block_size
    # Sliding window: pages below the tile's FIRST row's window start are
    # outside every row's window and are never fetched.
    win_eff = window_eff(win_ref[0])
    tile_lo = jnp.maximum(first + 1 - win_eff, 0)
    live = _LiveRange(
        row=b, table=b, first_page=tile_lo // block_size,
        n_pages=(limit + block_size - 1) // block_size,
        c_start=tile_lo // span, n_chunks=(limit + span - 1) // span,
    )

    @pl.when((b == 0) & (tq == 0))
    def _first_cell():
        _zero_values(buf)

    # A tile with no real row (a batch's padding row, the tiles past a
    # chunk's real length) neither streams nor folds: zeros, the drop-slot
    # contract.
    @pl.when(real == 0)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real > 0)
    def _tile():
        for h in range(KH):
            q_s[h] = q_ref[0, :, h * G : (h + 1) * G, :].reshape(
                n_sub, Rs, head_dim
            )
        _chunked_flash(
            live=live,
            layer=layer_ref[0],
            tables_ref=tables_ref,
            kv_hbm=kv_hbm,
            buf=buf,
            sems=sems,
            q_s=q_s,
            kv_s=kv_s,
            m_ref=m_ref,
            l_ref=l_ref,
            acc_ref=acc_ref,
            n_sub=(real * G + Rs - 1) // Rs,
            first=first,
            kv_len=kv_len,
            win_eff=win_eff,
            scale=scale,
            block_size=block_size,
            chunk=chunk,
            group=group,
            head_dim=head_dim,
            softcap=softcap,
            key_floor=key_floor,
        )
        # Rows past the real length return zeros whether their sub-tile
        # was folded beside real rows or not at all (l stays 0 there).
        rows = jax.lax.broadcasted_iota(jnp.int32, (Tq * G, 1), 0)
        is_real = rows // G < real
        for h in range(KH):
            out = acc_ref[h] / jnp.maximum(l_ref[h, :, :, :1], 1e-20)
            out = jnp.where(is_real, out.reshape(Tq * G, head_dim), 0.0)
            o_ref[0, :, h * G : (h + 1) * G, :] = out.reshape(
                Tq, G, head_dim
            ).astype(o_ref.dtype)


def _prefill_geometry(q_tile: int, group: int) -> "tuple[int, int]":
    """(sub-tiles a query tile, rows a sub-tile): ``_PREFILL_SUB_ROWS``
    rows of a head (whole positions, at least 8 of them) where that
    divides the tile, else the tile whole."""
    sub = max(_PREFILL_SUB_ROWS // group, 8)
    if q_tile % sub:
        sub = q_tile
    return q_tile // sub, sub * group


def _decode_geometry(q3, kv_pages, *, scale, softcap):
    """Shared decode-call geometry: chunking, flash scratch, and the kernel
    kwargs — ONE source of truth for the plain and fused-write wrappers
    (a tuning change here reaches both)."""
    B, H, hd = q3.shape
    _, nb, _, bs, lanes = kv_pages.shape
    KH = lanes // hd
    G = H // KH
    page_bytes = 2 * bs * lanes * kv_pages.dtype.itemsize
    C = min(_chunk_pages(bs, _DECODE_CHUNK_TOKENS),
            max(_DECODE_RING_BYTES // (_DECODE_SLOTS * page_bytes), 1))
    fold = _chunk_pages(bs, _DECODE_FOLD_TOKENS)
    C -= C % fold if C > fold else 0  # whole fold steps
    kwargs = dict(
        scale=scale, block_size=bs, chunk=C, group=G, head_dim=hd,
        softcap=softcap, fold_pages=fold if C > fold else 0,
    )
    scratch = [
        pltpu.VMEM((_DECODE_SLOTS, C, 2, bs, lanes), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((_DECODE_SLOTS, C)),
        # The stream's issuer and folder, carried from cell to cell
        # (``_page_dma_loop``).
        pltpu.SMEM((_STREAM_STATE_WORDS,), jnp.int32),
    ]
    flash_scratch = [
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, hd), jnp.float32),
    ]
    return B, H, hd, bs, lanes, C, kwargs, scratch, flash_scratch


def _decode_call(q3, kv_pages, block_tables, kv_lens, layer, window,
                 *, scale, softcap, key_floor=0, starts=None, positions=1,
                 static_window=0):
    """``q3``: a row's query lines, ``[B, positions x heads, hd]`` laid out
    ``[KH, positions, G]`` (``_decode_kernel``: a short run), with ``starts``
    the rows' first query positions; one position needs neither.
    ``static_window``: the window where the caller knows it at trace time: a
    call that cannot share traces no phase (``_SHARED_TILE_BYTES``: code a
    call holds costs its cells whether it runs or not)."""
    B, H, hd, bs, lanes, C, kw, scratch, flash = _decode_geometry(
        q3, kv_pages, scale=scale, softcap=softcap
    )
    kw.update(key_floor=key_floor, positions=positions)
    short = [] if starts is None else [starts]
    share = decode_shares(B, H, hd, static_window)
    q_all, q_all_spec, phase = [], [], []
    if share:
        # Every row's query whole beside the cell's own block (the same
        # array: its block never changes, so it is fetched once), and the
        # phase's state (``_SharedPhase``; ``wide``: in blocks of 128 lanes).
        q_all = [q3]
        q_all_spec = [pl.BlockSpec((B, H, hd), lambda b, *_: (0, 0, 0))]
        lb = 128 if hd % 128 == 0 else hd  # ``decode_shares``: on the chip, 128
        wide = pltpu.VMEM((hd // lb, B * H, lb), jnp.float32)
        narrow = pltpu.VMEM((B * H, 128), jnp.float32)
        phase = [pltpu.SMEM((2,), jnp.int32), wide, wide, narrow, narrow, wide]

    def kernel(tables_ref, lens_ref, layer_ref, win_ref, *refs):
        given, how = None, {}
        if short:
            how["starts_ref"], *refs = refs
        q_ref, *refs = refs
        if share:  # inputs, the output, scratch: in the order given below
            q_all_ref, *refs = refs
            given = _SharedPhase(q_all_ref, *refs[-len(phase):])
            refs = refs[:-len(phase)]
        _decode_kernel(tables_ref, lens_ref, layer_ref, win_ref, q_ref, *refs,
                       phase=given, **how, **kw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(short),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
            *q_all_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch + flash + phase,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            # Sequential: a cell hands its successor the ring with chunks
            # in flight and five SMEM words. Nothing is lost by it on the
            # one chip the program knows (v5e, one TensorCore a chip:
            # ``device.py::DEVICE_TABLE``, ``perf/peaks.json``); a chip
            # with two cores would want the rows split between them first.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=pallas_interpret(),
        # A name of its own for the short run: a trace and the benchmark's
        # ``kernel.paged_attn_decode_*`` metrics tell the two apart.
        name="paged_attn_short" if short else "paged_attn_decode",
    )(block_tables, kv_lens, layer, window, *short, q3, *q_all, kv_pages)


def _prefill_call(q, kv_pages, block_tables, kv_lens, starts, layer, window,
                  *, scale, q_tile, softcap, key_floor=0):
    B, T, H, hd = q.shape
    _, nb, _, bs, lanes = kv_pages.shape
    KH = lanes // hd
    G = H // KH
    n_tiles = T // q_tile
    n_sub, Rs = _prefill_geometry(q_tile, G)
    fold_dtype = _fold_dtype(kv_pages.dtype, q_tile * G, hd)
    page_bytes = 2 * bs * lanes * (2 * kv_pages.dtype.itemsize + fold_dtype.itemsize)
    C = min(_chunk_pages(bs, _PREFILL_CHUNK_TOKENS),
            max(_PREFILL_KV_BYTES // page_bytes, 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec(
                (1, q_tile, H, hd), lambda b, t, tt, l, s, ly, w: (b, t, 0, 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, q_tile, H, hd), lambda b, t, tt, l, s, ly, w: (b, t, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, C, 2, bs, lanes), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, C)),
            pltpu.VMEM((KH, n_sub, Rs, hd), q.dtype),
            pltpu.VMEM((2, C * bs, lanes), fold_dtype),
            pltpu.VMEM((KH, n_sub, Rs, 128), jnp.float32),
            pltpu.VMEM((KH, n_sub, Rs, 128), jnp.float32),
            pltpu.VMEM((KH, n_sub, Rs, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        scale=scale,
        block_size=bs,
        chunk=C,
        group=G,
        head_dim=hd,
        q_tile=q_tile,
        softcap=softcap,
        **({"key_floor": key_floor} if key_floor else {}),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Sequential, so that the first cell's ``_zero_values`` covers
            # the call (one TensorCore a chip on v5e: nothing is lost).
            dimension_semantics=("arbitrary", "arbitrary"),
            # The 256-position q tile, its flash state and the KV chunks
            # exceed the default 16 MiB scoped-vmem budget; the chip has
            # far more.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=pallas_interpret(),
        name="paged_attn_prefill",
    )(block_tables, kv_lens, starts, layer, window, q, kv_pages)


def pallas_paged_attention(
    q: jax.Array,  # [B, T, H, hd]
    kv_pages: jax.Array,  # [L, nb, 2, bs, KH*hd]
    block_tables: jax.Array,  # [B, W]
    kv_lens: jax.Array,  # [B]
    q_positions: jax.Array,  # [B, T] absolute positions (row 0 = chunk start)
    layer=0,  # int32 scalar (may be traced — e.g. the model's layer scan)
    *,
    scale: float,
    window=0,  # int32 scalar sliding window (may be traced; 0 = unlimited)
    softcap: float = 0.0,  # attention-logit soft cap (static; 0 = off)
    key_floor: int = 0,  # static: keys below it are masked for every query
) -> jax.Array:
    B, T, H, hd = q.shape
    tables = block_tables.astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    if T == 1:
        out = _decode_call(
            q[:, 0], kv_pages, tables, lens, layer_arr, win_arr,
            scale=scale, softcap=softcap, key_floor=key_floor,
        )
        return out[:, None]
    starts = q_positions[:, 0].astype(jnp.int32)
    if rides_stream(T, H):
        # A short run rides the decode stream as ``T x H`` query lines
        # ``[KH, T, G]``: the turn there and back is XLA's, a few MiB a step
        # beside the pages the call reads.
        KH = kv_pages.shape[-1] // hd
        lines = q.reshape(B, T, KH, H // KH, hd).transpose(0, 2, 1, 3, 4)
        out = _decode_call(
            lines.reshape(B, T * H, hd), kv_pages, tables, lens, layer_arr,
            win_arr, scale=scale, softcap=softcap, key_floor=key_floor,
            starts=starts, positions=T,
            static_window=window if isinstance(window, int) else 0,
        )
        return out.reshape(B, KH, T, H // KH, hd).transpose(
            0, 2, 1, 3, 4).reshape(B, T, H, hd)

    # Chunk positions are consecutive from row 0's position (the runner
    # builds prefill batches that way), so the kernel derives causality from
    # starts alone, and a row's real length from kv_len: the positions at and
    # past it are the bucket's padding, are not folded and return zeros
    # (their outputs are discarded downstream: last_idx / dropped writes).
    # 256-row q tiles: every tile re-streams the sequence's earlier KV, so
    # at long context halving the tile count halves attention HBM traffic.
    q_tile = min(T, 256)
    if T % q_tile:
        raise ValueError(
            f"pallas prefill needs a chunk length divisible by its q tile "
            f"({q_tile}), got T={T}; the runner only emits power-of-two "
            "chunk buckets"
        )
    return _prefill_call(
        q, kv_pages, tables, lens, starts, layer_arr, win_arr, scale=scale,
        q_tile=q_tile, softcap=softcap, key_floor=key_floor,
    )
