"""Pallas TPU flash kernels over paged KV: decode and chunked prefill.

The hot ops of the serving loop (the role vLLM's CUDA PagedAttention +
flash-attn kernels play behind the reference stack). Both are HBM-bandwidth
bound at the reference's long-context protocol (20k-token histories, 32k
max_model_len — ``BASELINE.md``), so the kernel is organized around DMA
efficiency, not grid geometry:

- KV lives in one combined page array ``[nb, 2, bs, KH*hd]`` (a page holds
  its K rows then V rows, each token row spanning **all** kv heads in the
  lane dimension), so one async copy moves an entire page — 100s of KB per
  DMA instead of the 8 KB per-head fragments a ``[KH, nb, bs, hd]`` layout
  forces. The head fold keeps the minor dims at ``(bs, KH*hd)``: both
  tiling-exact, no sublane padding (a ``[..., KH, hd]`` tail would pad
  KH=8 → 16 sublanes and physically double the cache).
- The grid is tiny — ``(B,)`` for decode, ``(B, T/Tq)`` for prefill — and
  each cell walks its sequence's **live** pages with a double-buffered
  ``fori_loop`` (chunks of ``C`` pages), overlapping the next chunk's DMAs
  with the current chunk's flash accumulation. Pages past ``kv_len`` — and,
  for prefill, pages entirely above the tile's causal horizon — are never
  fetched at all (the round-2 kernel's ``pl.when`` skipped the *compute* but
  the BlockSpec pipeline still paid the *DMA*; that was the round-2 TTFT
  regression).
- Flash state (m/l/acc) is head-major in VMEM scratch so per-head slices are
  contiguous; grouped-query heads share each page read.

Scalar-prefetched block tables address the pages (``PrefetchScalarGridSpec``)
so page ids are in SMEM before the body runs.

The kernels take the FULL stacked cache ``[L, nb, 2, bs, KH*hd]`` plus a
(possibly traced) layer index rather than a per-layer slice: inside the
model's layer scan a slice would materialize the whole 100s-of-MB layer
cache as a copy per layer per step, while the ANY-space operand costs
nothing — the DMA engine reads only the pages the sequence actually needs.

Shapes:
  q           [B, T, H, hd]        T=1 decode, T=chunk prefill
  kv_pages    [L, nb, 2, bs, KH*hd] combined K(row 0)/V(row 1) pages
  tables      [B, W] int32         page ids (W*bs >= kv_len)
  kv_lens     [B] int32            valid KV length per sequence (0 = padding)
  q_positions [B, T] int32         absolute position per query token; the
                                   prefill kernel uses row 0 (chunks are
                                   consecutive positions — runner contract)
  layer       int32 scalar         layer to read (scalar-prefetched)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import pallas_interpret
from .attention import window_eff

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


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


def _chunk_pages(bs: int, target_tokens: int) -> int:
    """Pages per DMA buffer slot (~target_tokens per chunk). Decode uses
    bigger chunks than prefill: its per-chunk fixed cost (fori iteration,
    semaphore waits, G-row flash updates on mostly-empty vregs) dominates
    at long context, while prefill's larger per-chunk compute amortizes it
    already — and prefill's VMEM budget also carries the big q tile."""
    return max(target_tokens // bs, 1)


def _page_dma_loop(
    *,
    b,  # batch index (program id)
    layer,  # int32 layer index into the stacked cache
    n_chunks,  # traced: chunks of C pages to stream (exclusive end)
    tables_ref,  # [B, W] SMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY
    buf,  # [2, C, 2, bs, KH*hd] VMEM scratch
    sems,  # [2, C] DMA semaphores
    chunk: int,
    table_width: int,
    compute_chunk,  # (page [C, 2, bs, KH*hd], chunk_index) -> None
    c_start=0,  # traced: first live chunk (sliding window skips below it)
):
    """Double-buffered page streaming shared by decode and prefill: chunk
    ``c+1``'s DMAs are in flight while ``compute_chunk`` folds chunk ``c``.
    Chunks below ``c_start`` (entirely outside a sliding window) are neither
    fetched nor folded."""
    C, W = chunk, table_width

    def dma(c, j, slot):
        # Page ids past the live range clamp to the table's last entry;
        # their columns are masked by the caller (only the ragged final
        # chunk fetches any).
        page = tables_ref[b, jnp.minimum(c * C + j, W - 1)]
        return pltpu.make_async_copy(
            kv_hbm.at[layer, page], buf.at[slot, j], sems.at[slot, j]
        )

    @pl.when(n_chunks > c_start)
    def _warmup():
        for j in range(C):
            dma(c_start, j, jax.lax.rem(c_start, 2)).start()

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        nslot = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)
        def _next():
            for j in range(C):
                dma(c + 1, j, nslot).start()

        for j in range(C):
            dma(c, j, slot).wait()
        compute_chunk(buf[slot], c)
        return 0

    jax.lax.fori_loop(c_start, n_chunks, body, 0)


def _chunked_flash(
    *,
    b, layer, n_chunks, tables_ref, kv_hbm, buf, sems,
    q_heads,  # list of KH arrays [R, hd] (native dtype)
    bounds,  # [R, 1] exclusive per-row attention bound (causality + kv_len)
    m_ref,  # [KH, R, 128] fp32 scratch (col 0 live)
    l_ref,  # [KH, R, 128]
    acc_ref,  # [KH, R, hd]
    scale: float,
    block_size: int,
    chunk: int,
    table_width: int,
    head_dim: int,
    lows=None,  # [R, 1] inclusive per-row lower bound (sliding window)
    softcap: float = 0.0,
    c_start=0,  # traced: first chunk any row's window reaches
):
    """Per-head flash accumulation over streamed KV chunks (the prefill
    shape: R = Tq*G rows per head keep the MXU busy per head). Matmuls run
    in the operands' native dtype with fp32 accumulation — MXU-native for
    the bf16 serving path, exact for the fp32 oracle tests."""
    hd = head_dim
    KH = acc_ref.shape[0]

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(page, c):
        S = chunk * block_size
        col = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        for h in range(KH):
            kh = page[:, 0, :, h * hd : (h + 1) * hd].reshape(S, hd)
            vh = page[:, 1, :, h * hd : (h + 1) * hd].reshape(S, hd)
            s = jax.lax.dot_general(
                q_heads[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [R, S] fp32
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            live = col < bounds
            if lows is not None:
                live = live & (col >= lows)
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h, :, :1] = alpha * l_ref[h, :, :1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            m_ref[h, :, :1] = m_new
            acc_ref[h] = acc_ref[h] * alpha + _pv_dot(p, vh)

    _page_dma_loop(
        b=b, layer=layer, n_chunks=n_chunks, tables_ref=tables_ref,
        kv_hbm=kv_hbm, buf=buf, sems=sems, chunk=chunk,
        table_width=table_width, compute_chunk=compute, c_start=c_start,
    )


def _decode_kernel(
    tables_ref, lens_ref, layer_ref, win_ref,  # scalar prefetch (SMEM)
    q_ref,  # [1, H, hd] VMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY
    o_ref,  # [1, H, hd] VMEM
    buf, sems, m_ref, l_ref, acc_ref,  # scratch (m/l [H,128], acc [H,hd])
    *,
    scale: float,
    block_size: int,
    chunk: int,
    table_width: int,
    group: int,
    head_dim: int,
    softcap: float = 0.0,
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
    full-vreg [H, S] pass."""
    b = pl.program_id(0)
    G, hd = group, head_dim
    H = q_ref.shape[1]
    KH = H // G
    kv_len = lens_ref[b]
    n_chunks = (kv_len + chunk * block_size - 1) // (chunk * block_size)
    # Sliding window (0 = unlimited): the one query row sits at position
    # kv_len-1 and may see positions >= kv_len - window; whole chunks below
    # that are never fetched.
    lo = jnp.maximum(kv_len - window_eff(win_ref[0]), 0)
    c_start = lo // (chunk * block_size)

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

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(page, c):
        S = chunk * block_size
        k = page[:, 0].reshape(S, KH * hd)
        v = page[:, 1].reshape(S, KH * hd)
        col = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        s = jax.lax.dot_general(
            q_sparse, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, S] fp32
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where((col >= lo) & (col < kv_len), s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = _pv_dot(p, v).reshape(H, KH, hd)
        own = (pv * blockdiag).sum(axis=1)  # each row's own head block
        acc_ref[...] = acc_ref[...] * alpha + own

    _page_dma_loop(
        b=b, layer=layer_ref[0], n_chunks=n_chunks, tables_ref=tables_ref,
        kv_hbm=kv_hbm, buf=buf, sems=sems, chunk=chunk,
        table_width=table_width, compute_chunk=compute, c_start=c_start,
    )
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)  # [H, hd]
    o_ref[0] = out.astype(o_ref.dtype)


def _decode_write_kernel(
    tables_ref, lens_ref, layer_ref, win_ref, wf_ref,  # scalar prefetch
    q_ref,  # [1, H, hd] VMEM
    k_ref,  # [1, 1, KH*hd] VMEM — this step's K row for this sequence
    v_ref,  # [1, 1, KH*hd] VMEM
    kv_hbm,  # [L, nb, 2, bs, KH*hd] ANY (aliased with kv_out)
    o_ref,  # [1, H, hd] VMEM
    kv_out,  # [L, nb, 2, bs, KH*hd] ANY — the SAME buffer (in-place)
    buf, sems, wbuf, wsems, m_ref, l_ref, acc_ref,
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
    per sequence per layer, noise next to the KV stream."""
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

    _decode_kernel(
        tables_ref, lens_ref, layer_ref, win_ref,
        q_ref, kv_out, o_ref, buf, sems, m_ref, l_ref, acc_ref, **kw,
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
        q3, kv_pages, block_tables, scale=scale, softcap=softcap
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
    buf, sems, m_ref, l_ref, acc_ref,  # scratch
    *,
    scale: float,
    block_size: int,
    chunk: int,
    table_width: int,
    group: int,
    head_dim: int,
    q_tile: int,
    softcap: float = 0.0,
):
    b = pl.program_id(0)
    tq = pl.program_id(1)
    G, Tq, KH = group, q_tile, acc_ref.shape[0]
    kv_len = lens_ref[b]
    start = starts_ref[b]

    # Rows t*G+g of each head cover absolute positions start + tq*Tq + t.
    # The tile's causal horizon is its last row's position; pages past
    # min(horizon+1, kv_len) are never fetched (≈ halves page traffic over a
    # full prefill, while warm tiles near the sequence end still stream every
    # live page — exactly the data they need).
    limit = jnp.minimum(kv_len, start + (tq + 1) * Tq)
    n_chunks = (limit + chunk * block_size - 1) // (chunk * block_size)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Tq * G, 1), 0)
    q_pos = start + tq * Tq + rows // G  # [Tq*G, 1]
    bounds = jnp.minimum(q_pos + 1, kv_len)
    # Sliding window lower bounds; chunks below the tile's FIRST row's
    # window start are outside every row's window and are never fetched.
    win_eff = window_eff(win_ref[0])
    lows = jnp.maximum(q_pos + 1 - win_eff, 0)  # [Tq*G, 1]
    c_start = jnp.maximum(start + tq * Tq + 1 - win_eff, 0) // (
        chunk * block_size
    )

    qh = [
        q_ref[0, :, h * G : (h + 1) * G, :].reshape(Tq * G, head_dim)
        for h in range(KH)
    ]
    _chunked_flash(
        b=b,
        layer=layer_ref[0],
        n_chunks=n_chunks,
        tables_ref=tables_ref,
        kv_hbm=kv_hbm,
        buf=buf,
        sems=sems,
        q_heads=qh,
        bounds=bounds,
        m_ref=m_ref,
        l_ref=l_ref,
        acc_ref=acc_ref,
        scale=scale,
        block_size=block_size,
        chunk=chunk,
        table_width=table_width,
        head_dim=head_dim,
        lows=lows,
        softcap=softcap,
        c_start=c_start,
    )
    # Padding rows (kv_len == 0) accumulated nothing: l stays 0 and the
    # output is 0, matching the drop-slot contract.
    for h in range(KH):
        out = acc_ref[h] / jnp.maximum(l_ref[h, :, :1], 1e-20)  # [Tq*G, hd]
        o_ref[0, :, h * G : (h + 1) * G, :] = out.reshape(
            Tq, G, head_dim
        ).astype(o_ref.dtype)


def _scratch(C, bs, lanes, R, KH, hd, kv_dtype):
    return [
        pltpu.VMEM((2, C, 2, bs, lanes), kv_dtype),
        pltpu.SemaphoreType.DMA((2, C)),
        pltpu.VMEM((KH, R, 128), jnp.float32),
        pltpu.VMEM((KH, R, 128), jnp.float32),
        pltpu.VMEM((KH, R, hd), jnp.float32),
    ]


def _decode_geometry(q3, kv_pages, block_tables, *, scale, softcap):
    """Shared decode-call geometry: chunking, flash scratch, and the kernel
    kwargs — ONE source of truth for the plain and fused-write wrappers
    (a tuning change here reaches both)."""
    B, H, hd = q3.shape
    _, nb, _, bs, lanes = kv_pages.shape
    KH = lanes // hd
    W = block_tables.shape[1]
    G = H // KH
    C = _chunk_pages(bs, 1024)
    kwargs = dict(
        scale=scale, block_size=bs, chunk=C, table_width=W, group=G,
        head_dim=hd, softcap=softcap,
    )
    scratch = [
        pltpu.VMEM((2, C, 2, bs, lanes), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2, C)),
    ]
    flash_scratch = [
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, hd), jnp.float32),
    ]
    return B, H, hd, bs, lanes, C, kwargs, scratch, flash_scratch


def _decode_call(q3, kv_pages, block_tables, kv_lens, layer, window,
                 *, scale, softcap):
    B, H, hd, bs, lanes, C, kw, scratch, flash = _decode_geometry(
        q3, kv_pages, block_tables, scale=scale, softcap=softcap
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, t, l, ly, w: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, t, l, ly, w: (b, 0, 0)),
        scratch_shapes=scratch + flash,
    )
    kernel = functools.partial(_decode_kernel, **kw)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=pallas_interpret(),
        name="paged_attn_decode",
    )(block_tables, kv_lens, layer, window, q3, kv_pages)


def _prefill_call(q, kv_pages, block_tables, kv_lens, starts, layer, window,
                  *, scale, q_tile, softcap):
    B, T, H, hd = q.shape
    _, nb, _, bs, lanes = kv_pages.shape
    KH = lanes // hd
    W = block_tables.shape[1]
    G = H // KH
    C = _chunk_pages(bs, 512)
    n_tiles = T // q_tile

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
        scratch_shapes=_scratch(C, bs, lanes, q_tile * G, KH, hd, kv_pages.dtype),
    )
    kernel = functools.partial(
        _prefill_kernel,
        scale=scale,
        block_size=bs,
        chunk=C,
        table_width=W,
        group=G,
        head_dim=hd,
        q_tile=q_tile,
        softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # The 256-row q tile + 512-token KV chunks exceed the default
            # 16 MiB scoped-vmem budget; the chip has far more.
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
) -> jax.Array:
    B, T, H, hd = q.shape
    tables = block_tables.astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    if T == 1:
        out = _decode_call(
            q[:, 0], kv_pages, tables, lens, layer_arr, win_arr,
            scale=scale, softcap=softcap,
        )
        return out[:, None]

    # Chunk positions are consecutive from row 0's position (the runner
    # builds prefill batches that way), so the kernel derives causality from
    # starts alone. Padding rows attend past their chunk; their outputs are
    # discarded downstream (last_idx / dropped writes).
    # 256-row q tiles: every tile re-streams the sequence's earlier KV, so
    # at long context halving the tile count halves attention HBM traffic.
    q_tile = min(T, 256)
    if T % q_tile:
        raise ValueError(
            f"pallas prefill needs a chunk length divisible by its q tile "
            f"({q_tile}), got T={T}; the runner only emits power-of-two "
            "chunk buckets"
        )
    starts = q_positions[:, 0].astype(jnp.int32)
    return _prefill_call(
        q, kv_pages, tables, lens, starts, layer_arr, win_arr, scale=scale,
        q_tile=q_tile, softcap=softcap,
    )
