"""Pallas paged-attention kernels (interpret mode on CPU) vs the gather oracle.

KV layout: one combined page array [nb, 2, bs, KH*hd] (K rows at index 0 of
the pair dim, V rows at index 1; heads folded into the lane dim) — the
layout the kernels DMA whole pages of.
"""

import pytest
import numpy as np
import jax
import jax.numpy as jnp

from production_stack_tpu.ops.attention import gather_paged_attention
from production_stack_tpu.ops.paged_attention_pallas import pallas_paged_attention


def _pack(k, v):
    # [KH, nb, bs, hd] pair -> stacked combined [L=1, nb, 2, bs, KH*hd]
    KH, nb, bs, hd = k.shape
    fold = lambda x: x.transpose(1, 2, 0, 3).reshape(nb, bs, KH * hd)
    return np.stack([fold(k), fold(v)], axis=1)[None]


def _setup(B=3, H=8, KH=4, hd=32, nb=32, bs=8, W=4, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, 1, H, hd), dtype=np.float32)
    k = rng.standard_normal((KH, nb, bs, hd), dtype=np.float32)
    v = rng.standard_normal((KH, nb, bs, hd), dtype=np.float32)
    # Distinct pages per sequence; varying kv lengths.
    tables = rng.permutation(nb)[: B * W].reshape(B, W).astype(np.int32)
    kv_lens = np.array([5, bs * W, bs * 2 + 3], np.int32)[:B]
    q_pos = (kv_lens - 1).reshape(B, 1).astype(np.int32)
    return map(jnp.asarray, (q, _pack(k, v), tables, kv_lens, q_pos))


def test_pallas_decode_matches_gather():
    q, kv, tables, kv_lens, q_pos = _setup()
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.fixture(params=["shared_phase_traced", "per_row_walk_traced"])
def decode_trace(request, monkeypatch):
    """The two traces of a decode call at more than one row. Under the
    interpreter every such call traces the shared phase
    (``decode_shares``: the stream's places are the run, then the rows); on
    the chip a call whose rows or heads are not in eights, or whose heads
    are not of 128 lanes, traces the walk a row with the next row's first
    chunk fetched ahead. Both are held against the oracle."""
    from production_stack_tpu.ops import paged_attention_pallas as pap

    if request.param == "per_row_walk_traced":
        monkeypatch.setattr(pap, "decode_shares", lambda *shape, **kw: False)
    return request.param


def test_pallas_handles_empty_rows(decode_trace):
    q, kv, tables, kv_lens, q_pos = _setup()
    kv_lens = kv_lens.at[1].set(0)  # padding row
    scale = 1.0 / np.sqrt(q.shape[-1])
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    assert np.all(np.isfinite(np.asarray(got)))
    assert np.allclose(np.asarray(got)[1], 0.0)


# --- the page stream at the kernels' own geometry -------------------------
# 128-token pages (8 a decode or a prefill chunk) over 64 lanes, so a
# row has one, two or several chunks and the loop's second iteration, the
# ragged last chunk and the hand-over between rows all run. Every table
# entry the kernel has no business fetching (past the row's last live page,
# below its sliding window) points at a page of NaN: a dead page that is
# fetched, or whose columns are not masked, poisons the output. The oracle
# gets the same table with those entries pointed at page 0 (it masks scores
# but multiplies every gathered V).

_BS, _KH, _HD, _H = 128, 2, 32, 4
_NAN_PAGE = 1


def _stream_case(lens, *, T=1, window=0, dtype=jnp.float32, layers=1, layer=0,
                 seed=0, real=None, softcap=0.0):
    """``real``: tokens a row's ``T``-token bucket really holds (the runner
    pads a chunk to a power of two; ``lens`` ends at the last real one)."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    lens = np.asarray(lens, np.int32)
    real = np.full(B, T, np.int32) if real is None else np.asarray(real, np.int32)
    W = max(-(-int(lens.max()) // _BS), 1) + 3  # dead entries in every row
    nb = 2 + B * W
    kv = rng.standard_normal((layers, nb, 2, _BS, _KH * _HD)).astype(np.float32)
    kv[:, _NAN_PAGE] = np.nan
    kv[:layer] = np.nan  # a read of the wrong layer fails
    q = rng.standard_normal((B, T, _H, _HD), dtype=np.float32)
    tables = (2 + rng.permutation(B * W)).reshape(B, W).astype(np.int32)
    q_pos = (lens - real)[:, None] + np.arange(T, dtype=np.int32)[None]
    first = np.maximum(q_pos[:, 0] + 1 - window, 0) // _BS if window else 0
    page = np.arange(W)[None]
    dead = (page >= -(-lens // _BS)[:, None]) | (page < np.reshape(first, (-1, 1)))
    kv = jnp.asarray(kv).astype(dtype)
    rest = (jnp.asarray(lens), jnp.asarray(q_pos), layer)
    kw = dict(scale=1.0 / np.sqrt(_HD), window=window, softcap=softcap)
    ref = gather_paged_attention(
        jnp.asarray(q), kv, jnp.asarray(np.where(dead, 0, tables)),
        *rest, **kw)
    got = pallas_paged_attention(
        jnp.asarray(q), kv, jnp.asarray(np.where(dead, _NAN_PAGE, tables)),
        *rest, **kw)
    return np.asarray(got), np.asarray(ref), lens


_DECODE_STREAMS = {
    # kv_len an exact multiple of the 1,024-token chunk
    "whole_chunks": dict(lens=[1024, 3072, 2048]),
    # one live page (and one live token) in the last chunk
    "one_live_page_in_last_chunk": dict(lens=[1024 + 5, 2048 + 128, 17]),
    # slot parity across the row boundary, both ways round
    "one_chunk_then_many": dict(lens=[300, 3500]),
    "many_chunks_then_one": dict(lens=[3500, 300]),
    "odd_and_even_chunk_counts": dict(lens=[1100, 3000, 900, 2100, 2049]),
    # decode padding: nothing issued, nothing waited for, output 0
    "empty_row_first": dict(lens=[0, 1500, 700]),
    "empty_row_last": dict(lens=[1500, 700, 0]),
    "empty_row_between": dict(lens=[1500, 0, 0, 2300]),
    "all_rows_empty": dict(lens=[0, 0, 0]),
    # c_start 2, 0, 1: the next row's first chunk is fetched at ITS start,
    # and its first live page lies mid-chunk
    "window_start_differs_by_row": dict(lens=[3000, 1300, 2500], window=600),
    "fp8_pages": dict(lens=[1100, 2100, 40], dtype=jnp.float8_e4m3fn),
    "soft_cap": dict(lens=[1100, 40, 2100], softcap=30.0),
    "layer_of_a_stack": dict(lens=[1300, 200], layers=3, layer=2),
    "single_row": dict(lens=[2500]),
    # A short run (PR 54): two query positions a row ride the same stream,
    # a bound a line. Row 2's pair straddles a chunk's (and a page's) edge.
    "short_run_of_two": dict(lens=[1100, 0, 2049, 3000, 17], T=2),
    # row 0's second position is padding (at ``kv_len``): zeros, its page dead
    "short_run_with_a_padding_position": dict(
        lens=[1025, 700, 2], T=2, real=[1, 2, 2]),
    # c_start 2, 0, 1 again; position 0's window starts a column lower
    "short_run_under_a_window": dict(lens=[3000, 1300, 2500], T=2, window=600),
    "short_run_of_four_fp8_pages": dict(
        lens=[1100, 2100, 40], T=4, dtype=jnp.float8_e4m3fn),
}


def _real_positions(kw, lens):
    """[B, T] bool: the query positions of a stream case that hold a token."""
    T = kw.get("T", 1)
    real = np.asarray(kw.get("real", [T] * len(lens)))
    return (np.arange(T)[None] < real[:, None]) & (lens > 0)[:, None]


@pytest.mark.parametrize("case", list(_DECODE_STREAMS))
def test_pallas_decode_streams_live_pages_only(case, decode_trace):
    kw = _DECODE_STREAMS[case]
    got, ref, lens = _stream_case(**kw)
    real = _real_positions(kw, lens)
    assert np.all(np.isfinite(got)), "a dead or foreign page reached the fold"
    assert np.all(got[~real] == 0.0)  # the drop-slot contract
    # fp8 pages: the kernel's probabilities carry ~2^-8 (the split dot)
    tol = 2e-2 if "dtype" in kw else 2e-5
    np.testing.assert_allclose(got[real], ref[real], rtol=tol, atol=tol)


@pytest.mark.parametrize("case", [
    "odd_and_even_chunk_counts", "empty_row_between",
    "window_start_differs_by_row", "short_run_of_two",
    "short_run_with_a_padding_position",
])
def test_pallas_decode_every_wait_meets_its_copy(
        case, decode_trace, monkeypatch):
    """The plain interpreter copies at ``start`` and ignores ``wait``, so a
    wait that names another copy than the one started (what hangs the chip,
    or lets a fold read a slot still being filled) passes there. JAX's TPU
    interpreter moves the bytes at the ``wait``: a chunk whose wait does
    not match its start, slot for slot and page for page, folds stale data
    here. The issuer runs rows ahead of the folder, so this is where their
    agreement is held."""
    from jax.experimental.pallas import tpu as pltpu
    from production_stack_tpu.ops import paged_attention_pallas as pap

    monkeypatch.setattr(
        pap, "pallas_interpret",
        lambda: pltpu.InterpretParams(dma_execution_mode="on_wait"))
    got, ref, lens = _stream_case(**_DECODE_STREAMS[case])
    real = _real_positions(_DECODE_STREAMS[case], lens)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[real], ref[real], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case, kw", [
    # 16 query rows ending at 716 and at 1,540: the tile's limit leaves
    # two, and three, of the last chunk's eight pages dead
    ("limit_ends_mid_chunk", dict(lens=[716, 1540], T=16)),
    # window 300 over rows ending at 716: positions below 401 are outside
    # every row's window, so the chunk's first three pages are dead too
    ("window_starts_mid_chunk", dict(lens=[716, 1540], T=16, window=300)),
])
def test_pallas_prefill_streams_live_pages_only(case, kw):
    got, ref, _ = _stream_case(**kw)
    assert np.all(np.isfinite(got)), "a dead or foreign page reached the fold"
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# The prefill fold (PR 34): sub-tiles of 256 rows of a head (128 positions
# at this group of 2), a chunk's K and V widened to bf16 once where pages
# are e4m3 and the rows are many, nothing folded for the query positions
# past a row's real length. A chunk spans 1,024 tokens here as on the chip.
_PREFILL_FOLDS = {
    # R = 512 rows a head against 2 x hd = 64: pages widened to bf16 (the
    # chunk's rows permuted, the mask's columns with them), one p @ V
    "fp8_pages_widened": dict(lens=[1500], T=256, dtype=jnp.float8_e4m3fn),
    "fp8_pages_padded_bucket": dict(
        lens=[1390], T=256, real=[150], dtype=jnp.float8_e4m3fn),
    "fp8_pages_widened_window_softcap": dict(
        lens=[2400], T=64, window=500, softcap=30.0, dtype=jnp.float8_e4m3fn),
    # R = 32 < 64: the pages stay fp8 and the split product is kept
    "fp8_pages_few_rows_keep_split": dict(
        lens=[700, 90], T=16, dtype=jnp.float8_e4m3fn),
    # start 1,100 is not a multiple of 1,024: chunk 0 is whole and live
    # for every row, chunk 1 holds the causal boundary
    "continuation_off_the_chunk_grid": dict(lens=[1164], T=64),
    # window 700: chunk 0 is cut from below (three dead pages, then rows
    # whose windows start at different columns), chunk 1 from above
    "continuation_window_softcap": dict(
        lens=[1164], T=64, window=700, softcap=30.0),
    # window 300 over 2,900: chunks 0 and 1 are not fetched, chunk 2 is cut
    # from below and from above at once
    "window_inside_one_chunk": dict(lens=[2900], T=64, window=300),
    "real_length_1": dict(lens=[901], T=256, real=[1]),
    "real_length_sub_tile_edge": dict(lens=[1028], T=256, real=[128]),
    "real_length_one_past_edge": dict(lens=[1029], T=256, real=[129]),
    "real_length_full_bucket": dict(lens=[1156], T=256, real=[256]),
    # two tiles of 256: the second holds no token, streams and folds nothing
    "last_tile_all_padding": dict(lens=[800], T=512, real=[200]),
    "empty_rows_beside_live": dict(
        lens=[0, 1164, 0, 600], T=64, real=[0, 64, 0, 40]),
    "fresh_prompt_in_four_tiles": dict(lens=[700], T=1024, real=[700]),
}


@pytest.mark.parametrize("case", list(_PREFILL_FOLDS))
def test_pallas_prefill_folds_real_rows_only(case):
    kw = _PREFILL_FOLDS[case]
    got, ref, lens = _stream_case(**kw)
    real = kw.get("real", [kw["T"]] * len(lens))
    assert np.all(np.isfinite(got)), "a dead or foreign page reached the fold"
    # float32 pages stay exact; fp8 pages carry bf16 probabilities
    tol = 2e-2 if "dtype" in kw else 2e-5
    for b, n in enumerate(real):
        np.testing.assert_allclose(got[b, :n], ref[b, :n], rtol=tol, atol=tol)
        assert np.all(got[b, n:] == 0.0), "padding rows return zeros"


def test_widen_e4m3_is_exact_for_every_value():
    """Every e4m3 byte but the two NaNs, subnormals and both zeros among
    them, comes out as the bf16 of the same value, at the row
    ``_widened_rows`` names."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        _widen_e4m3, _widened_rows)

    pats = np.array([b for b in range(256) if b & 0x7F != 0x7F], np.uint8)
    S = 64
    raw = np.random.default_rng(0).choice(pats, size=(S, 256))
    raw[:, 0] = pats[:S]
    raw[:, 1] = pats[S : 2 * S]
    raw[:, 2] = pats[2 * S : 3 * S]
    raw[: len(pats) - 3 * S, 3] = pats[3 * S :]
    x = jax.lax.bitcast_convert_type(jnp.asarray(raw), jnp.float8_e4m3fn)
    got = jnp.concatenate(jax.jit(_widen_e4m3)(x), axis=0)  # a kernel op: jit
    assert got.dtype == jnp.bfloat16
    rows = np.asarray(_widened_rows(S))[0]
    assert sorted(rows) == list(range(S))
    want = np.asarray(x.astype(jnp.float32))[rows]
    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    assert np.array_equal(np.signbit(got), np.signbit(want))  # -0.0 too


def test_pallas_prefill_widens_pages_by_the_shapes_it_sees():
    """One-byte pages are widened where the probability tile outweighs the
    chunk's K and V slices, and only there; decode's split product stays
    for a handful of rows, and wider pages are folded as they are."""
    from production_stack_tpu.ops.paged_attention_pallas import _fold_dtype

    fp8, bf16 = jnp.float8_e4m3fn, jnp.bfloat16
    assert _fold_dtype(fp8, rows=256 * 4, head_dim=128) == bf16
    assert _fold_dtype(fp8, rows=128 * 4, head_dim=128) == bf16
    assert _fold_dtype(fp8, rows=4 * 4, head_dim=128) == fp8  # a verify step
    assert _fold_dtype(bf16, rows=1024, head_dim=128) == bf16
    assert _fold_dtype(jnp.float32, rows=1024, head_dim=128) == jnp.float32
    # another one-byte format keeps the split product: the widening on the
    # packed words reads e4m3's fields
    assert _fold_dtype(jnp.float8_e5m2, rows=1024, head_dim=128) == jnp.float8_e5m2


def _prefill_setup(B, T, start_offsets, H=8, KH=4, hd=32, nb=64, bs=8, W=8,
                   seed=1):
    """Chunked-prefill batch: row b's chunk starts at start_offsets[b] and
    covers T consecutive positions; KV for [0, start+T) is resident."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, T, H, hd), dtype=np.float32)
    k = rng.standard_normal((KH, nb, bs, hd), dtype=np.float32)
    v = rng.standard_normal((KH, nb, bs, hd), dtype=np.float32)
    tables = rng.permutation(nb)[: B * W].reshape(B, W).astype(np.int32)
    starts = np.asarray(start_offsets, np.int32)
    kv_lens = starts + T  # chunk KV already written (cache = source of truth)
    q_pos = starts[:, None] + np.arange(T, dtype=np.int32)[None]
    return map(jnp.asarray, (q, _pack(k, v), tables, kv_lens, q_pos))


def test_pallas_prefill_matches_gather_fresh_prompt():
    q, kv, tables, kv_lens, q_pos = _prefill_setup(B=2, T=16, start_offsets=[0, 0])
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_prefill_matches_gather_chunk_continuation():
    # Later chunks (prefix-cache hit or chunked prefill continuation): the
    # chunk starts mid-sequence and attends to all earlier KV.
    q, kv, tables, kv_lens, q_pos = _prefill_setup(
        B=3, T=8, start_offsets=[0, 13, 40]
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_prefill_long_context():
    # Long-history shape: 1 row, 64-token chunk at the end of ~1.5k-token
    # context (interpret mode keeps this CPU-feasible; real sizes on TPU).
    q, kv, tables, kv_lens, q_pos = _prefill_setup(
        B=1, T=64, start_offsets=[1472], nb=256, W=192
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_prefill_multi_tile():
    # T > q_tile (128): multiple query tiles per row; later tiles must apply
    # the causal horizon so early-page traffic is skipped without changing
    # the math.
    q, kv, tables, kv_lens, q_pos = _prefill_setup(
        B=1, T=256, start_offsets=[64], nb=128, W=64
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_pallas_prefill_odd_tile_falls_back():
    # T not divisible by the 128-row tile: falls back to gather (runner
    # buckets are powers of two, so this only happens for exotic callers).
    q, kv, tables, kv_lens, q_pos = _prefill_setup(
        B=1, T=192, start_offsets=[0], nb=128, W=32
    )
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# slow: 28 s a case interpreted, for a path that only PST_FUSED_KV_WRITE
# selects (no default and no benchmark cell runs it).
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float8_e4m3fn])
def test_decode_write_fused_matches_scatter_then_read(dtype):
    """The fused write+attend decode kernel must equal scatter-then-read
    exactly: same cache bytes, same attention output (incl. the drop
    sentinel row and an fp8 cache)."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        pallas_paged_attention,
        pallas_paged_attention_decode_write,
    )

    rng = np.random.default_rng(0)
    L, nb, bs, KH, hd, G = 2, 32, 8, 2, 16, 4
    H, lanes = KH * G, KH * 16
    B, W = 3, 6
    kv = jnp.asarray(rng.standard_normal((L, nb, 2, bs, lanes)), dtype)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    # Disjoint per-row pages (the allocator's ownership invariant).
    tables = jnp.asarray((np.arange(B * W).reshape(B, W) % nb).astype(np.int32))
    lens_l = [13, 1, 40]
    lens = jnp.asarray(lens_l, jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((B, lanes)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, lanes)), jnp.float32)
    wf = []
    for i, ln in enumerate(lens_l):
        p = ln - 1
        wf.append(int(tables[i, p // bs]) * bs + p % bs)
    wf[1] = nb * bs  # row 1: drop sentinel (padding rows never write)
    wf = jnp.asarray(wf, jnp.int32)
    layer = 1

    kv_ref = np.asarray(kv.astype(jnp.float32)).copy()
    for i in range(B):
        w = int(wf[i])
        if w < nb * bs:
            kv_ref[layer, w // bs, 0, w % bs] = np.asarray(k_new)[i]
            kv_ref[layer, w // bs, 1, w % bs] = np.asarray(v_new)[i]
    kv_ref = jnp.asarray(kv_ref, dtype)
    ref = pallas_paged_attention(
        q[:, None], kv_ref, tables, lens, (lens - 1)[:, None], layer,
        scale=0.25,
    )

    out, kv_out = pallas_paged_attention_decode_write(
        q, kv, tables, lens, layer, k_new, v_new, wf, scale=0.25
    )
    np.testing.assert_array_equal(
        np.asarray(kv_out.astype(jnp.float32)),
        np.asarray(kv_ref.astype(jnp.float32)),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref[:, 0]), atol=1e-5
    )


# --- the shared phase of decode (PR 50) -------------------------------------
# Rows behind one prompt hold the same leading pages; the kernel streams
# those once, folds them against every row's query at once, and each row's
# walk goes on from there (``_decode_kernel``). Held here: that the result is
# the per-row walk's (the same call with the run forced to 0) to the
# rounding of the pages' dtype, and the gather oracle's; that the shared
# pages really are read through ONE row's table (every other row's entries
# for them point at NaN); and where the run ends.

_SHARED_GEOMETRIES = {
    # the looped cell's: every head its own keys and values, 32-token pages
    "bf16_mha_pages_of_32": dict(
        H=4, KH=4, hd=32, bs=32, dtype=jnp.bfloat16, tol=2e-2),
    # the dense cell's: four query heads a KV head, 128-token e4m3 pages
    "fp8_grouped_4_to_1_pages_of_128": dict(
        H=8, KH=2, hd=32, bs=128, dtype=jnp.float8_e4m3fn, tol=2e-2),
    "float32_exact": dict(H=4, KH=2, hd=32, bs=32, dtype=jnp.float32, tol=2e-5),
}
# (pages the rows' tables hold in common, own tokens a row; the shortest
# row's own tokens decide how far the run may reach)
_SHARED_RUNS = {
    "run_0": dict(common=0, own=[40, 70, 5, 33]),
    "partial_run": dict(common=3, own=[40, 70, 5, 33]),
    # the tables agree on five pages, but row 2 ends inside the fifth: the
    # run stops before the page it writes
    "whole_of_the_shortest_row_but_its_last_page": dict(
        common=5, own=[40, 70, -3, 33]),
    "padding_rows_inside_the_batch": dict(
        common=3, own=[40, None, 70, None, 5, 33, None, 9]),
    "run_longer_than_a_chunk": dict(common=70, own=[40, 70, 5, 33]),
    # row 0's last two tokens are the fourth page's last and the fifth's
    # first: as a short run of two its positions straddle the boundary and
    # the run ends a page lower than for its one query
    "last_two_tokens_straddle_a_page_boundary": dict(
        common=4, own=[1, 40, 70, 33]),
    # row 0's second-last token opens its fifth page: the run ends exactly
    # where a short run of two begins
    "run_ends_at_the_first_of_two_positions": dict(
        common=4, own=[2, 40, 70, 33]),
}


def _shared_case(*, H, KH, hd, bs, dtype, common, own, softcap=0.0, seed=0,
                 tol=None, poison_other_rows=False, ahead=0, T=1):
    """Rows whose tables agree on ``common`` pages, then ``own[i]`` tokens
    each (negative: the row ends that many tokens before the common pages
    do; None: a padding row, ``kv_len`` 0, its table zeros; ``ahead``: tokens
    a burst will add, whose pages are live too; ``T``: query positions a row,
    the last ``T`` of its tokens). -> (inputs of ``pallas_paged_attention``,
    tables for the oracle, lens, pages shared)."""
    rng = np.random.default_rng(seed)
    B = len(own)
    lens = np.array(
        [0 if o is None else common * bs + o for o in own], np.int32)
    W = -(-int(lens.max()) // bs) + 2
    nb = 2 + common + B * W
    kv = rng.standard_normal((2, nb, 2, bs, KH * hd)).astype(np.float32)
    kv[:, 1] = np.nan
    kv[0] = np.nan  # layer 1 is read
    tables = (2 + common + rng.permutation(B * W)).reshape(B, W).astype(np.int32)
    tables[:, :common] = 2 + np.arange(common)
    tables[lens == 0] = 0
    live = lens > 0
    dead = np.arange(W)[None] >= -(-(lens + ahead * live) // bs)[:, None]
    pages = min(common, int((lens[live] - T).min()) // bs) if live.sum() > 1 else 0
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    if dtype != jnp.float32:
        q = q.astype(jnp.bfloat16)
    seen = np.where(dead, 1, tables)
    if poison_other_rows:
        first = int(np.argmax(live))
        others = np.arange(B) != first
        seen[np.ix_(others, np.arange(pages))] = 1
    q_pos = np.maximum(lens - T, 0)[:, None] + np.arange(T, dtype=np.int32)
    args = (q, jnp.asarray(kv).astype(dtype), jnp.asarray(seen),
            jnp.asarray(lens), jnp.asarray(q_pos), 1)
    kw = dict(scale=1.0 / np.sqrt(hd), softcap=softcap)
    return args, jnp.asarray(np.where(dead, 0, tables)), lens, pages, kw


def program_run(tables, lens, bs, starts=None):
    """(pages, first live row) as the decode kernel's first cell finds them:
    ``_find_shared_run`` on the tables and lengths in SMEM (and a short
    run's first query positions, ``starts``), in a kernel of its own."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from production_stack_tpu.ops import paged_attention_pallas as pap

    scalars = [tables, lens] + ([] if starts is None else [starts])

    def kernel(tables_ref, lens_ref, *refs):
        *starts_ref, out_ref = refs
        out_ref[0], out_ref[1] = pap._find_shared_run(
            tables_ref, lens_ref, tables.shape[0], bs, *starts_ref)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,), in_specs=[],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((2,), jnp.int32),
        interpret=True,
    )(*(jnp.asarray(x, jnp.int32) for x in scalars))
    return tuple(int(x) for x in np.asarray(out))


def _per_row_walk(monkeypatch):
    """The same call with the run forced to 0: today's walk, a row a cell."""
    from production_stack_tpu.ops import paged_attention_pallas as pap

    monkeypatch.setattr(
        pap, "_find_shared_run", lambda *refs: (jnp.int32(0), jnp.int32(0)))


def _three_ways(args, oracle_tables, kw, monkeypatch):
    """(the kernel with its shared phase, the gather oracle, the per-row
    walk) on one set of inputs, float32."""
    got = np.asarray(pallas_paged_attention(*args, **kw), np.float32)
    ref = np.asarray(gather_paged_attention(
        args[0], args[1], oracle_tables, *args[3:], **kw), np.float32)
    _per_row_walk(monkeypatch)
    walk = np.asarray(pallas_paged_attention(*args, **kw), np.float32)
    return got, ref, walk


@pytest.mark.parametrize("run", list(_SHARED_RUNS))
@pytest.mark.parametrize("geometry", list(_SHARED_GEOMETRIES))
@pytest.mark.parametrize("T", [1, 2], ids=["one_position", "short_run_of_two"])
def test_pallas_decode_shared_phase_equals_the_per_row_walk(
        T, geometry, run, monkeypatch):
    """``T`` 2 (PR 54): the phase folds every row's two positions' lines a
    KV head, and the run ends below each row's first position."""
    geo = dict(_SHARED_GEOMETRIES[geometry])
    tol = geo.pop("tol")
    args, oracle_tables, lens, pages, kw = _shared_case(
        **geo, **_SHARED_RUNS[run], T=T)
    found = program_run(args[2], args[3], geo["bs"],
                        None if T == 1 else args[4][:, 0])
    assert found[0] == pages and (pages == 0 or lens[found[1]] > 0)
    got, ref, walk = _three_ways(args, oracle_tables, kw, monkeypatch)
    live = lens > 0
    assert np.all(np.isfinite(got)), "a dead or foreign page reached the fold"
    assert np.all(got[~live] == 0.0)  # the drop-slot contract
    np.testing.assert_allclose(got[live], walk[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(got[live], ref[live], rtol=tol, atol=tol)
    if pages == 0:  # a run of 0 is today's call, bit for bit
        assert np.array_equal(got, walk)


@pytest.mark.parametrize("geometry", list(_SHARED_GEOMETRIES))
def test_pallas_decode_shared_phase_with_a_soft_cap(geometry, monkeypatch):
    geo = dict(_SHARED_GEOMETRIES[geometry])
    tol = geo.pop("tol")
    args, oracle_tables, lens, pages, kw = _shared_case(
        **geo, common=3, own=[40, 70, None, 33], softcap=20.0, seed=3)
    got, ref, walk = _three_ways(args, oracle_tables, kw, monkeypatch)
    live = lens > 0
    np.testing.assert_allclose(got[live], walk[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(got[live], ref[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("interpreter", ["plain", "copies_at_the_wait"])
def test_pallas_decode_reads_shared_pages_through_one_row(
        interpreter, monkeypatch):
    """Every row but the first live one has NaN pages where the run stands
    in its table: a shared page fetched a row would poison that row. And
    under the interpreter that moves the bytes at the wait, the shared
    chunks ride the same ring ahead of row 0's: every wait meets its copy."""
    from jax.experimental.pallas import tpu as pltpu
    from production_stack_tpu.ops import paged_attention_pallas as pap

    geo = dict(_SHARED_GEOMETRIES["float32_exact"])
    tol = geo.pop("tol")
    args, oracle_tables, lens, pages, kw = _shared_case(
        **geo, common=70, own=[None, 40, 70, 5, 2100], poison_other_rows=True)
    assert pages == 70
    # the run is what the real tables say; the kernel is handed the poisoned
    monkeypatch.setattr(
        pap, "_find_shared_run", lambda *refs: (jnp.int32(70), jnp.int32(1)))
    if interpreter != "plain":
        monkeypatch.setattr(
            pap, "pallas_interpret",
            lambda: pltpu.InterpretParams(dma_execution_mode="on_wait"))
    got = np.asarray(pallas_paged_attention(*args, **kw), np.float32)
    ref = np.asarray(gather_paged_attention(
        args[0], args[1], oracle_tables, *args[3:], **kw), np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[1:], ref[1:], rtol=tol, atol=tol)


def test_pallas_decode_shared_phase_is_not_taken_under_a_window(monkeypatch):
    """A window bounds a row's reads already and its shared pages may lie
    below it: with ``window`` > 0 the rows walk alone, whatever the run."""
    geo = dict(_SHARED_GEOMETRIES["float32_exact"])
    geo.pop("tol")
    args, _, lens, pages, kw = _shared_case(
        **geo, common=6, own=[40, 70, 5, 33])
    assert pages == 6
    got = np.asarray(pallas_paged_attention(*args, window=100, **kw))
    _per_row_walk(monkeypatch)
    walk = np.asarray(pallas_paged_attention(*args, window=100, **kw))
    assert np.array_equal(got, walk)


def test_pallas_decode_shared_phase_through_a_burst(monkeypatch):
    """A burst of depth 3 inside one program: the lengths advance on the
    device and the kernel finds the run again at every step (row 2 crosses onto a
    new page at the third step, which lets the run grow by one)."""
    geo = dict(_SHARED_GEOMETRIES["bf16_mha_pages_of_32"])
    tol, bs = geo.pop("tol"), geo["bs"]
    args, oracle_tables, lens, pages, kw = _shared_case(
        **geo, common=5, own=[40, 70, -1, 33], ahead=2)
    assert pages == 4
    q, kv, tables, lens_j, _, layer = args

    def burst(q, kv, tables, lens):
        def step(lens, _):
            out = pallas_paged_attention(
                q, kv, tables, lens, (lens - 1)[:, None], layer, **kw)
            return lens + 1, out
        return jax.lax.scan(step, lens, None, length=3)[1]

    got = np.asarray(jax.jit(burst)(q, kv, tables, lens_j), np.float32)
    runs = [program_run(tables, lens_j + i, bs)[0] for i in range(3)]
    assert runs == [4, 4, 5]
    _per_row_walk(monkeypatch)
    walk = np.asarray(jax.jit(burst)(q, kv, tables, lens_j), np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, walk, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Two query positions a row (a verify-and-draft step) and a key floor (a
# layer whose entries are stored one slot ahead: slot 0 empty and masked)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,window,key_floor", [
    (2, 0, 0),  # a verify step through a full-attention layer
    (2, 16, 0),  # through a window layer
    (2, 0, 1),  # through the draft layer: slot 0 masked
    (1, 0, 1),  # one position under the floor: the decode stream's mask
    (16, 0, 1),  # a prefill chunk under the floor
    # (PR 54) 4 x 8 heads: still a short run of the decode stream
    (4, 0, 0), (4, 16, 0), (4, 0, 1), (4, 5, 1),
    (32, 0, 1),  # 256 lines: the chunk kernel
])
def test_pallas_verify_shapes_and_the_key_floor_match_gather(T, window, key_floor):
    """Rows whose chunk starts at a page boundary, inside a page, at the
    sequence's start (where the floor leaves the first query one key) and
    deep into it; the kernel equals the gather reference, and under the
    floor slot 0 is not read as a key whatever it holds."""
    # (eight pages of 8 a row: a chunk ends by token 64)
    starts = [1 if key_floor else 0, 13, 40, 48] if T <= 16 else [1, 5, 20, 32]
    q, kv, tables, kv_lens, q_pos = _prefill_setup(
        B=4, T=T, start_offsets=starts)
    if key_floor:  # a wild slot 0: masked, so it moves nothing
        kv = kv.at[:, tables[:, 0], :, 0].set(1e4)
    scale = 1.0 / np.sqrt(q.shape[-1])
    kw = dict(scale=scale, window=window)
    ref = gather_paged_attention(q, kv, tables, kv_lens, q_pos,
                                 key_floor=key_floor, **kw)
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos,
                                 key_floor=key_floor, **kw)
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(ref)).max() < 50
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
    if key_floor:  # and without the floor the wild slot is read
        wild = gather_paged_attention(q, kv, tables, kv_lens, q_pos, **kw)
        assert np.abs(np.asarray(wild) - np.asarray(ref)).max() > 1


def _traced_calls(monkeypatch):
    """Every ``pallas_call`` the kernels' module makes from here on: (name,
    scratch shapes)."""
    from production_stack_tpu.ops import paged_attention_pallas as pap

    calls, real = [], pap.pl.pallas_call

    def spy(kernel, *, name, grid_spec, **kw):
        calls.append((name, [
            getattr(x, "shape", None) for x in grid_spec.scratch_shapes]))
        return real(kernel, name=name, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(pap.pl, "pallas_call", spy)
    return calls


def test_the_kernel_a_call_traces_is_chosen_by_its_shape_alone(monkeypatch):
    """``rides_stream``: one position is ``paged_attn_decode``; a short run
    whose ``T x H`` lines one block-diagonal fold takes is the same stream
    under a name of its own; past that the chunk kernel. And one position
    traces what it traced before the short run came: the ring, the flash
    state and the phase's six buffers, sized by ``H`` lines."""
    from production_stack_tpu.ops import paged_attention_pallas as pap

    assert pap.rides_stream(1, 512) and pap.rides_stream(2, 64)
    assert pap.rides_stream(4, 32) and not pap.rides_stream(4, 64)
    assert not pap.rides_stream(256, 4)
    calls = _traced_calls(monkeypatch)
    for T, want in ((1, "paged_attn_decode"), (2, "paged_attn_short"),
                    (16, "paged_attn_short"), (32, "paged_attn_prefill")):
        q, kv, tables, kv_lens, q_pos = _prefill_setup(
            B=2, T=T, start_offsets=[3, 20])
        jax.eval_shape(
            lambda *a: pallas_paged_attention(*a, scale=1.0),
            q, kv, tables, kv_lens, q_pos)
        assert calls[-1][0] == want, (T, calls[-1][0])
    H, hd, B, bs, lanes = 8, 32, 2, 8, 4 * 32
    one, two = calls[0][1], calls[1][1]
    C = one[0][1]
    assert one == [
        (pap._DECODE_SLOTS, C, 2, bs, lanes), (pap._DECODE_SLOTS, C), (5,),
        (H, 128), (H, 128), (H, hd),  # a row's flash state
        (2,), (1, B * H, hd), (1, B * H, hd), (B * H, 128), (B * H, 128),
        (1, B * H, hd)]  # the shared phase's
    # the short run's is the same list at twice the lines
    assert two[:3] == one[:3] and two[3:] == [
        tuple(2 * n if n in (H, B * H) else n for n in shape)
        for shape in one[3:]]


def test_a_short_run_on_the_chip_takes_the_phase_by_its_line_count(monkeypatch):
    """``decode_sharing_calls`` asked with the positions a row: the verify
    step of the draft cell (64 rows x 2 x 64 heads) is one sharing call; a
    run too long for the stream is the chunk kernel's and shares nothing."""
    from production_stack_tpu.ops import paged_attention_pallas as pap
    from production_stack_tpu.ops.attention import decode_sharing_calls

    monkeypatch.setattr(pap, "pallas_interpret", lambda: False)
    monkeypatch.delenv("PST_FUSED_KV_WRITE", raising=False)
    assert decode_sharing_calls("pallas", None, 64, 64, 128, 0, 2) == 1
    assert decode_sharing_calls("pallas", None, 64, 64, 128, 128, 2) == 0
    assert decode_sharing_calls("pallas", None, 64, 64, 128, 0, 4) == 0
    assert decode_sharing_calls("pallas", None, 16, 32, 128, 0, 4) == 1
    assert decode_sharing_calls("pallas", None, 4, 32, 128, 0, 2) == 0
    assert decode_sharing_calls("gather", None, 64, 64, 128, 0, 2) == 0
