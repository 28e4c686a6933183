"""Attention over a paged cache of latents (multi-head latent attention).

A token's cache row in a layer is ``[c_kv (rank) | k_rope (rope) | 0...]``:
the normalised KV latent and the one rotated key all heads share, padded to
a whole number of 128-lane tiles (``latent_lanes``: 512 + 64 -> 640; a
576-lane minor dimension is laid out in 640 lanes on the chip anyway, so the
padding is written down where the pool is sized). Pages are
``[L, nb, 1, bs, lanes]``: one row a token, **keys are the whole row and
values are its first ``rank`` lanes**, so a page is read once, and all the
heads are rows of the same two products.

Three ways through the same pages:

- :func:`mla_decode` — the Pallas kernel of a decode step (``%mla_decode``
  on the device trace). Absorbed form: queries already carry ``W_uk``
  (``q~_h = W_uk_h^T q_nope_h``), scores are taken against the latent rows
  themselves and the result is the softmax-weighted sum of latents, to which
  the caller applies ``W_uv``. Per row it streams the layer's live pages
  once through the ring of ``paged_attention_pallas._page_dma_loop`` (issuer
  ahead of folder, live pages only, the next row's first chunk in flight
  while this row's last is folded) and folds ``[heads, lanes] x [lanes,
  chunk]`` scores and ``p @ chunk[:, :rank]`` with an online softmax in
  float32.
- :func:`absorbed_attention` — the same mathematics in ``jax.numpy`` for any
  number of query positions a row (a short question over a long cached
  document: nothing of the context is expanded), and the decode step where
  no kernel is compiled (the CPU tests).
- :func:`expanded_attention` — keys and values rebuilt from the latents with
  ``W_uk`` / ``W_uv``, a block of the context at a time (a long fresh
  prompt: 1,024 operations a head and token pair against the absorbed
  2,176, once the expansion is paid).

Both ``jax.numpy`` paths walk the context in blocks of pages up to the
longest live row and keep a float32 online softmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import pallas_interpret
from .paged_attention_pallas import (
    _DECODE_CHUNK_TOKENS, _DECODE_FOLD_TOKENS, _DECODE_SLOTS, _NEG_INF,
    _STREAM_STATE_WORDS, _LiveRange, _chunk_pages, _page_dma_loop,
)

# Context tokens a block of the jax.numpy paths gathers and folds at once.
BLOCK_TOKENS = 1024
# Query rows of the kernel: the heads padded to whole sublane tiles of a
# two-byte dtype.
_ROW_TILE = 16


def latent_lanes(rank: int, rope: int) -> int:
    """Lanes of a cache row: ``rank + rope`` up to a whole 128-lane tile."""
    return -(-(rank + rope) // 128) * 128


def write_rows(cache: jax.Array, layer, flat_write: jax.Array,
               rows: jax.Array) -> jax.Array:
    """Scatter this step's rows ``[N, lanes]`` into ``layer``'s pages at
    flat slots ``flat_write [N]`` (``nb * bs`` and beyond: dropped)."""
    n_l, nb, _, bs, lanes = cache.shape
    idx = jnp.where(flat_write >= nb * bs, n_l * nb * bs,
                    layer * (nb * bs) + flat_write)
    return (
        cache.reshape(n_l * nb * bs, lanes)
        .at[idx].set(rows.astype(cache.dtype), mode="drop")
        .reshape(cache.shape)
    )


def _blocks(cache, layer, block_tables, kv_lens, fold, carry):
    """Walk each row's context in blocks of ``BLOCK_TOKENS``: ``fold(carry,
    latents [B, S, lanes], first position of the block) -> carry``, up to
    the longest live row (a dynamic trip count: dead blocks cost nothing)."""
    n_l, nb, _, bs, lanes = cache.shape
    B, W = block_tables.shape
    bp = max(min(BLOCK_TOKENS // bs, W), 1)
    n_blocks = -(-W // bp)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_blocks * bp - W)))
    flat = cache.reshape(n_l * nb, bs, lanes)
    S = bp * bs

    def body(i, carry):
        tb = jax.lax.dynamic_slice(tables, (0, i * bp), (B, bp))
        # one gather over the flattened page axis: no copy of the layer
        lat = flat[layer * nb + tb].reshape(B, S, lanes)
        return fold(carry, lat, i * S)

    live = (jnp.max(kv_lens) + S - 1) // S
    return jax.lax.fori_loop(0, jnp.minimum(live, n_blocks), body, carry)


def _online(carry, s, value_of):
    """One online-softmax step: ``s [..., S]`` float32 scores (masked
    columns at ``_NEG_INF``), ``value_of(p) -> [..., dv]``."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # a fully masked row so far: exp(_NEG_INF - _NEG_INF) = 1 per column
    p = jnp.where(s <= _NEG_INF, 0.0, p)
    alpha = jnp.exp(m - m_new)
    return (m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True),
            acc * alpha + value_of(p))


def absorbed_attention(
    q_abs: jax.Array,  # [B, T, H, rank + rope]: [W_uk^T q_nope | q_rope]
    cache: jax.Array,  # [L, nb, 1, bs, lanes]
    layer,
    block_tables: jax.Array,  # [B, W]
    kv_lens: jax.Array,  # [B]
    positions: jax.Array,  # [B, T]
    *,
    rank: int,
    scale: float,
) -> jax.Array:
    """-> float32 ``[B, T, H, rank]``: per head the softmax-weighted sum of
    the latents (the caller applies ``W_uv``). Heads are rows: every head
    and position of a sequence meets the same keys."""
    B, T, H, C = q_abs.shape
    f32 = jnp.float32
    q = q_abs.reshape(B, T * H, C)
    pos = jnp.repeat(positions.astype(jnp.int32), H, axis=1)[..., None]  # [B, TH, 1]
    bound = jnp.minimum(pos + 1, kv_lens.astype(jnp.int32)[:, None, None])

    def fold(carry, lat, start):
        keys = lat[..., :C].astype(q.dtype)
        s = jnp.einsum("bmc,bsc->bms", q, keys,
                       preferred_element_type=f32) * scale
        col = start + jnp.arange(lat.shape[1], dtype=jnp.int32)[None, None, :]
        s = jnp.where(col < bound, s, _NEG_INF)
        vals = lat[..., :rank]
        return _online(carry, s, lambda p: jnp.einsum(
            "bms,bsc->bmc", p.astype(vals.dtype), vals,
            preferred_element_type=f32))

    carry = (jnp.full((B, T * H, 1), _NEG_INF, f32),
             jnp.zeros((B, T * H, 1), f32), jnp.zeros((B, T * H, rank), f32))
    _, l, acc = _blocks(cache, layer, block_tables, kv_lens, fold, carry)
    return (acc / jnp.maximum(l, 1e-20)).reshape(B, T, H, rank)


def expanded_attention(
    q_nope: jax.Array,  # [B, T, H, nope]
    q_rope: jax.Array,  # [B, T, H, rope]
    w_uk: jax.Array,  # [H, nope, rank]
    w_uv: jax.Array,  # [H, rank, v]
    cache: jax.Array,
    layer,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    positions: jax.Array,
    *,
    scale: float,
) -> jax.Array:
    """-> float32 ``[B, T, H, v]``: keys and values of each context block
    rebuilt from its latents, then plain attention."""
    B, T, H, _ = q_nope.shape
    rank, dv = w_uv.shape[1], w_uv.shape[2]
    rope = q_rope.shape[-1]
    f32 = jnp.float32
    pos = positions.astype(jnp.int32)[:, None, :, None]  # [B, 1, T, 1]
    bound = jnp.minimum(pos + 1, kv_lens.astype(jnp.int32)[:, None, None, None])
    # One product over nope + rope a head (whole MXU tiles at 192 + 64), the
    # heads as the batch: the shared rotated key is repeated to every head.
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)

    def fold(carry, lat, start):
        ckv = lat[..., :rank]
        S = lat.shape[1]
        with jax.named_scope("mla_expand"):
            k_nope = jnp.einsum("bsc,hnc->bhsn", ckv, w_uk,
                                preferred_element_type=f32).astype(ckv.dtype)
            v = jnp.einsum("bsc,hcv->bhsv", ckv, w_uv,
                           preferred_element_type=f32).astype(ckv.dtype)
            k_rope = jnp.broadcast_to(
                lat[:, None, :, rank:rank + rope], (B, H, S, rope))
            k = jnp.concatenate([k_nope, k_rope.astype(ckv.dtype)], axis=-1)
        s = jnp.einsum("bhtd,bhsd->bhts", q.astype(k.dtype), k,
                       preferred_element_type=f32) * scale
        col = start + jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
        s = jnp.where(col < bound, s, _NEG_INF)
        return _online(carry, s, lambda p: jnp.einsum(
            "bhts,bhsv->bhtv", p.astype(v.dtype), v,
            preferred_element_type=f32))

    carry = (jnp.full((B, H, T, 1), _NEG_INF, f32),
             jnp.zeros((B, H, T, 1), f32), jnp.zeros((B, H, T, dv), f32))
    _, l, acc = _blocks(cache, layer, block_tables, kv_lens, fold, carry)
    return (acc / jnp.maximum(l, 1e-20)).transpose(0, 2, 1, 3)


# ----------------------------------------------------------------------------
# The decode kernel
# ----------------------------------------------------------------------------


def _mla_decode_kernel(
    tables_ref, lens_ref, layer_ref,  # scalar prefetch (SMEM)
    q_ref,  # [1, Hp, lanes] VMEM: [q~ | q_rope | 0], heads padded to Hp
    kv_hbm,  # [L, nb, 1, bs, lanes] ANY
    o_ref,  # [1, Hp, rank] VMEM
    buf, sems, state, m_ref, l_ref, acc_ref,
    *,
    scale: float,
    block_size: int,
    chunk: int,
    fold_pages: int,
    rank: int,
):
    """One grid cell a row; the grid is sequential and hands the ring of
    chunk slots, with copies in flight, from cell to cell (as
    ``paged_attention_pallas._decode_kernel``). A chunk is folded as keys
    (every lane) and as values (the first ``rank`` lanes) from the same
    buffer."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    span = chunk * block_size

    def rng(row):
        n = lens_ref[row]
        return _LiveRange(
            row=row, table=row, first_page=0, n_pages=(n + block_size - 1) // block_size,
            c_start=0, n_chunks=(n + span - 1) // span)

    kv_len = lens_ref[b]

    @pl.when(b == 0)
    def _first_cell():
        # A chunk's dead pages are not copied and their columns get p = 0
        # exactly; VMEM no copy of this call has written may hold a NaN
        # pattern, and 0 x NaN in p @ values is NaN. Values are the keys'
        # own buffer here, so all of it is zeroed once a call.
        zero = jnp.zeros(buf.shape[2:], buf.dtype)
        for slot in range(buf.shape[0]):
            for j in range(buf.shape[1]):
                buf[slot, j] = zero
        state[0] = -1  # the issuer points nowhere: this cell starts cold
        for i in range(1, _STREAM_STATE_WORDS):
            state[i] = 0

    q = q_ref[0]  # [Hp, lanes]
    lanes = q.shape[-1]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(page, col0):
        page = page[...]
        S = page.shape[0] * block_size
        k = page[:, 0].reshape(S, lanes)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Hp, S]
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Hp, rank]
        acc_ref[...] = acc_ref[...] * alpha + pv

    _page_dma_loop(
        live=rng(b), layer=layer_ref[0], tables_ref=tables_ref, kv_hbm=kv_hbm,
        buf=buf, sems=sems, chunk=chunk, compute_chunk=compute,
        fold_pages=fold_pages, across_rows=(state, rng, B),
    )
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)
    o_ref[0] = out.astype(o_ref.dtype)


def mla_decode(
    q_abs: jax.Array,  # [B, H, rank + rope]
    cache: jax.Array,  # [L, nb, 1, bs, lanes]
    block_tables: jax.Array,  # [B, W]
    kv_lens: jax.Array,  # [B] (0 = padding row: its result is 0)
    layer,
    *,
    rank: int,
    scale: float,
    chunk_tokens: int = _DECODE_CHUNK_TOKENS,
    fold_tokens: int = _DECODE_FOLD_TOKENS,
    slots: int = _DECODE_SLOTS,
) -> jax.Array:
    """-> ``[B, H, rank]`` in ``q_abs``'s dtype: the absorbed decode step
    over the layer's live pages, each read once."""
    B, H, C = q_abs.shape
    _, nb, _, bs, lanes = cache.shape
    Hp = -(-H // _ROW_TILE) * _ROW_TILE
    q = jnp.pad(q_abs.astype(cache.dtype), ((0, 0), (0, Hp - H), (0, lanes - C)))
    chunk = _chunk_pages(bs, chunk_tokens)
    fold = _chunk_pages(bs, fold_tokens)
    chunk -= chunk % fold if chunk > fold else 0
    kernel = functools.partial(
        _mla_decode_kernel, scale=scale, block_size=bs, chunk=chunk,
        fold_pages=fold if chunk > fold else 0, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, lanes), lambda b, t, l, ly: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hp, rank), lambda b, t, l, ly: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((slots, chunk, 1, bs, lanes), cache.dtype),
            pltpu.SemaphoreType.DMA((slots, chunk)),
            pltpu.SMEM((_STREAM_STATE_WORDS,), jnp.int32),
            pltpu.VMEM((Hp, 128), jnp.float32),
            pltpu.VMEM((Hp, 128), jnp.float32),
            pltpu.VMEM((Hp, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # the cells share the ring
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=pallas_interpret(),
        name="mla_decode",
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, cache)
    return out[:, :H]
