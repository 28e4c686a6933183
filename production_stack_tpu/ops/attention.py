"""Paged attention over a block-table KV cache.

This is the op the reference delegates to vLLM's CUDA PagedAttention; here it
is TPU-native with two interchangeable implementations:

- ``gather``: pure-XLA. Gathers the sequence's KV pages into a contiguous
  ``[B, S, ...]`` view and runs masked attention. Compiles everywhere
  (including the 8-device virtual CPU mesh used in tests) and XLA fuses the
  mask/softmax chain; the gather materialization costs HBM bandwidth, which
  rules it out at long context (a 32k-table gather materializes the whole
  window per layer).
- ``pallas``: TPU flash kernels that stream only the live pages HBM→VMEM
  with double-buffered DMA
  (:mod:`production_stack_tpu.ops.paged_attention_pallas`).

Shapes:
  q            [B, T, H, hd]       T=1 for decode rows, T=chunk for prefill
                                   (a verify step's few positions a row are
                                   a chunk of consecutive positions too)
  kv_pages     [L, nb, 2, bs, KH*hd] combined pages: row 0 = K, row 1 = V;
                                   each token row spans all kv heads in the
                                   lane dim (one DMA per page in the kernel;
                                   minor dims stay tiling-exact). The FULL
                                   stacked cache is passed with a layer
                                   index — a per-layer slice inside the
                                   model's layer scan would materialize a
                                   copy of the layer cache every step.
  block_tables [B, W] int32        page ids per sequence (W*bs >= kv_len)
  kv_lens      [B]   int32         valid KV length per sequence
  q_positions  [B, T] int32        absolute position of each query token
                                   (padding rows may hold any value; they are
                                   masked out downstream via last_idx/sampling)
  layer        int32 scalar        layer to attend against (may be traced)
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_DATA, AXIS_TENSOR

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def window_eff(window) -> jax.Array:
    """Effective sliding window as an int32 scalar: the configured window,
    or a past-any-context sentinel when 0/negative (= unlimited). Shared by
    the gather path, both Pallas kernels, and the encode path so the
    window-bound convention (`key_pos > q_pos - window_eff`) lives in one
    place."""
    win = jnp.asarray(window, jnp.int32)
    return jnp.where(win > 0, win, jnp.int32(1 << 30))


def resolve_attn_impl(impl: str) -> str:
    """``auto`` by platform: the kernels on ``tpu``, the gather reference
    on the CPU. Nothing else selects between them — a kernel that cannot
    compile is an error at the call, not a detour to the reference."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "gather"
    if impl not in ("pallas", "gather"):
        raise ValueError(f"unknown attn_impl {impl!r} (auto|gather|pallas)")
    return impl


def decode_write_fused(impl: str) -> bool:
    """Whether single-token decode should fold the KV write into the
    Pallas attention kernel (skips the per-layer XLA scatter).

    OFF by default: measured on v5e at the 8B bench shape, the fold's
    page round-trip (sub-row DMA into a tiled fp8 page is not
    expressible, so the kernel pulls/splices/pushes the whole page) costs
    MORE than the XLA scatter it removes (36.2 vs 32.5 ms/step at batch
    8 x 20k). Kept behind PST_FUSED_KV_WRITE=1 with its exact-parity test
    for revisiting on hardware where row-granular HBM writes are legal."""
    if os.environ.get("PST_FUSED_KV_WRITE") != "1":
        return False
    return resolve_attn_impl(impl) == "pallas"


def _row_shards(mesh, rows: int) -> int:
    """Shards a decode batch's rows fall into on ``mesh``: the data axis
    where the rows divide by it (``_pallas_per_shard``), else one."""
    dp = mesh.shape.get(AXIS_DATA, 1) if mesh is not None else 1
    return dp if dp > 1 and rows % dp == 0 else 1


def decode_sharing_calls(
    impl: str, mesh, rows: int, heads: int, head_dim: int, window: int = 0,
    positions: int = 1,
) -> int:
    """Into how many kernel calls that each read their rows' common
    leading pages once (``paged_attention_pallas.py``'s shared phase) a
    layer's decode attention over a bucket of ``rows`` rows falls: 0 where
    every call walks a row (the gather reference, the fused write, a shape
    or a window the phase does not take: ``decode_shares`` is the rule),
    else a call a shard of rows. What the engine's count of spared reads
    asks (``engine/runner.py::_step_info``): it holds no rule of its own.
    ``window``: that of the layers over the pages in question with the
    widest view (0: some layer reads a row's whole context). ``positions``:
    the query positions a row (a verify step's two or few); a run too long
    for the decode stream (``rides_stream``) is the chunk kernel's, which
    walks a row."""
    if resolve_attn_impl(impl) != "pallas" or decode_write_fused(impl):
        return 0
    from .paged_attention_pallas import decode_shares, rides_stream

    shards = _row_shards(mesh, rows)
    tp = mesh.shape.get(AXIS_TENSOR, 1) if mesh is not None else 1
    if not rides_stream(positions, heads // tp):
        return 0
    return shards if decode_shares(
        rows // shards, positions * heads // tp, head_dim, window) else 0


def paged_attention(
    q: jax.Array,
    kv_pages: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    q_positions: jax.Array,
    layer=0,
    *,
    scale: float,
    impl: str = "auto",
    window=0,
    softcap: float = 0.0,
    mesh=None,
    key_floor: int = 0,
) -> jax.Array:
    """Causal attention of ``q`` against paged KV. Returns [B, T, H, hd].

    ``window`` (int32 scalar, may be traced — e.g. derived from the layer
    index for Gemma-2's alternating local/global layers) limits each query
    to the last ``window`` positions; 0 = unlimited. ``softcap`` applies
    Gemma-style attention-logit soft-capping ``tanh(s/c)*c`` (static; 0 =
    off). ``mesh``: the engine mesh when it spans more than one device —
    the kernel then runs once per shard (see :func:`_pallas_per_shard`).
    ``key_floor`` (static): keys at positions below it are masked for every
    query (a layer whose entries are stored one slot ahead leaves slot 0
    empty: ``models/exaone_moe.py``'s draft layer): a column mask in the
    reference and in every kernel; one device only."""
    impl = resolve_attn_impl(impl)
    if impl == "pallas":
        if key_floor:
            from .paged_attention_pallas import pallas_paged_attention

            if mesh is not None and mesh.size > 1:
                raise ValueError("key_floor is served on one device")
            return pallas_paged_attention(
                q, kv_pages, block_tables, kv_lens, q_positions, layer,
                scale=scale, window=window, softcap=softcap,
                key_floor=key_floor,
            )
        if mesh is not None and mesh.size > 1:
            return _pallas_per_shard(
                q, kv_pages, block_tables, kv_lens, q_positions, layer,
                mesh=mesh, scale=scale, window=window, softcap=softcap,
            )
        from .paged_attention_pallas import pallas_paged_attention

        return pallas_paged_attention(
            q, kv_pages, block_tables, kv_lens, q_positions, layer,
            scale=scale, window=window, softcap=softcap,
        )
    return gather_paged_attention(
        q, kv_pages, block_tables, kv_lens, q_positions, layer, scale=scale,
        window=window, softcap=softcap, key_floor=key_floor,
    )


def _pallas_per_shard(
    q, kv_pages, block_tables, kv_lens, q_positions, layer,
    *, mesh, scale, window, softcap,
) -> jax.Array:
    """The Pallas kernels on a multi-device mesh. A Mosaic kernel cannot be
    partitioned by GSPMD at all — on the chip, lowering one under ``jit``
    over more than one device is an error unless EVERY mesh axis is manual
    around it — so the call is wrapped in a ``shard_map`` over all axes
    (all that are not manual already, inside a ``pp`` stage). Attention is
    independent per kv head and per sequence: the query heads (``H`` of
    ``q``) and the page lanes (``KH*hd`` of ``kv_pages``) are sharded over
    ``tp`` on the same head boundaries, rows over ``dp`` when the batch
    divides (decode buckets do; prefill chunks arrive replicated), so each
    shard runs the kernel on its own heads, rows and pages and nothing —
    in particular not the cache — crosses devices."""
    from .paged_attention_pallas import pallas_paged_attention

    ctx_mesh = jax.sharding.get_abstract_mesh()  # empty under plain jit
    axes = set(mesh.axis_names) - set(ctx_mesh.manual_axes)
    rows = (
        AXIS_DATA
        if AXIS_DATA in axes and _row_shards(mesh, q.shape[0]) > 1
        else None
    )
    heads = P(rows, None, AXIS_TENSOR, None)

    def per_shard(q, kv_pages, block_tables, kv_lens, q_positions, layer,
                  window):
        return pallas_paged_attention(
            q, kv_pages, block_tables, kv_lens, q_positions, layer,
            scale=scale, window=window, softcap=softcap,
        )

    return jax.shard_map(
        per_shard,
        # Nested in a pp stage: its context mesh, where pp is manual already.
        mesh=mesh if ctx_mesh.empty else None,
        in_specs=(
            heads, P(None, None, None, None, AXIS_TENSOR), P(rows, None),
            P(rows), P(rows, None), P(), P(),
        ),
        out_specs=heads,
        axis_names=axes,
        check_vma=False,
    )(
        q, kv_pages, block_tables, kv_lens, q_positions,
        jnp.asarray(layer, jnp.int32), jnp.asarray(window, jnp.int32),
    )


def gather_paged_attention(
    q: jax.Array,
    kv_pages: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    q_positions: jax.Array,
    layer=0,
    *,
    scale: float,
    window=0,
    softcap: float = 0.0,
    key_floor: int = 0,
) -> jax.Array:
    B, T, H, hd = q.shape
    _, nb, _, bs, lanes = kv_pages.shape
    KH = lanes // hd
    W = block_tables.shape[1]
    S = W * bs
    G = H // KH

    # [W...] -> [B, S, KH, hd] per half. Out-of-range table entries are
    # clipped by XLA gather semantics; they are masked below anyway. (The
    # layer slice materializes here — acceptable for the test/CPU path.)
    pages = jax.lax.dynamic_index_in_dim(kv_pages, layer, 0, keepdims=False)
    kv = pages[block_tables]
    k = kv[:, :, 0].reshape(B, S, KH, hd)
    v = kv[:, :, 1].reshape(B, S, KH, hd)

    qg = q.reshape(B, T, KH, G, hd)
    # scores [B, KH, G, T, S]
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap

    kv_pos = jnp.arange(S, dtype=jnp.int32)[None, :]  # [1, S]
    valid = kv_pos < kv_lens[:, None]  # [B, S]
    causal = kv_pos[:, None, :] <= q_positions[..., None]  # [B, T, S]
    # Sliding window: each query sees at most the last `window` positions
    # (0 = unlimited; `window` may be a traced scalar for per-layer windows).
    in_window = kv_pos[:, None, :] > q_positions[..., None] - window_eff(window)
    if key_floor:
        in_window &= kv_pos[:, None, :] >= key_floor
    mask = (valid[:, None, :] & causal & in_window)[:, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    # 1-byte (fp8/int8) caches: the PV dot runs in the query dtype —
    # casting probs to the cache dtype would quantize the softmax weights
    # themselves (model-level numerics oracle regression).
    dt = q.dtype if jnp.dtype(v.dtype).itemsize == 1 else v.dtype
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(dt), v.astype(dt),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, H, hd).astype(q.dtype)
