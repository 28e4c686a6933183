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


def test_pallas_handles_empty_rows():
    q, kv, tables, kv_lens, q_pos = _setup()
    kv_lens = kv_lens.at[1].set(0)  # padding row
    scale = 1.0 / np.sqrt(q.shape[-1])
    got = pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=scale)
    assert np.all(np.isfinite(np.asarray(got)))
    assert np.allclose(np.asarray(got)[1], 0.0)


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
