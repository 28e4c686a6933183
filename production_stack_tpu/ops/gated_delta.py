"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): the recurrence of
a linear-attention layer whose state is a **matrix** a head, corrected by a
rank-one term a token.

Per head, with state ``S [K, V]`` (key x value, float32, zero at a
sequence's start), a token's ``q, k [K]`` (L2-normalised, ``q`` scaled),
``v [V]``, log-decay ``g <= 0`` and ``beta`` in (0, 1)::

    S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

Every other recurrence this repository runs decays a vector-valued state
elementwise (``ops/ssm.py``, ``ops/selective_scan.py``); here a token reads
the state (``S^T k``) before it writes it, which is why the chunked form
needs a triangular solve a chunk.

The pool is ``[layers, slots, H, K, V]`` float32: a head's keys on the
sublanes, its values on the lanes. The kernels read a row's block of a pool
by its slot through scalar prefetch and write it back in place
(``input_output_aliases``): no copy of a pool.

- :func:`gated_delta_decode` (``%gated_delta_decode``): one position a row,
  on the vector unit; every row's ``[H, K, V]`` state read and written once.
- :func:`gated_delta_prefill` (``%gated_delta_prefill``): the chunked (WY /
  UT-transform) form on the matrix unit at chunks of ``CHUNK`` positions.
  Inside a chunk ``(I + N) U = beta V`` with ``N`` the strictly lower part
  of ``(beta K K^T) * decay``; ``N`` is nilpotent, so ``(I + N)^-1 = (I +
  M)(I + M^2)(I + M^4)...`` with ``M = -N`` is exact after ``log2(CHUNK)``
  factors: products of ``CHUNK x CHUNK`` matrices, no row-by-row
  substitution. Between chunks the state is carried in fast memory. Packed
  rows each start from their own slot (a fresh row from zeros), chunks
  past a row's true length are not walked, and positions of padding inside
  a row's last chunk have ``g = 0`` and ``beta = 0``, which leaves the
  state as it is.
- :func:`conv_tail_decode` (``%conv_tail_decode``): a decode step's causal
  convolution over ``[tail | this step's row]`` on the second pool, the
  convolution's tails (:func:`tail_shape`: a tap of a slot's tail is whole
  ``[sublane, lane]`` tiles, so a slot is contiguous and nothing is
  padded): each row's tail read, shifted by the row and written back.

:func:`delta_reference` is the recurrence position by position in
``jax.numpy``: the CPU path, and the tests' oracle for the kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import pallas_interpret

# Positions a prefill grid cell solves at once (arXiv:2412.06464 §3 runs 64).
CHUNK = 64
# Heads a decode grid cell takes: 8 x 64 KiB of state in, as much out, twice
# for the double buffer.
DECODE_HEADS = 8
LANES = 128
# What ``conv_tail_decode`` claims of the 128 MiB of fast memory, though its
# blocks hold under 1 MiB: the tails' pool (43 MB at the served widths) fits
# there, and XLA, left the room, carries the whole pool in and out around
# every call (two passes of the pool a layer where 6 MB move; PERF.md §6,
# PR 43). ``ops/int4_matmul.py`` keeps its scales' stack out the same way. (A
# memory-space constraint on the aliased operand says it outright, and aborts
# XLA's memory-space assignment wherever the pool is not donated.)
TAILS_VMEM_LIMIT_BYTES = 112 << 20
_HI = jax.lax.Precision.HIGHEST


def delta_reference(s0, q, k, v, g, beta):
    """``s0 [B, H, K, V]`` float32; ``q``/``k [B, T, H, K]``, ``v [B, T, H,
    V]``, ``g``/``beta [B, T, H]`` -> ``(o [B, T, H, V] float32, s_T)``. A
    position with ``g`` = 0 and ``beta`` = 0 leaves the state as it is."""
    f32 = jnp.float32

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp  # [B, H, K] x2, [B, H, V], [B, H] x2
        s = jnp.exp(g_t)[..., None, None] * s
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HI))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), s


def use_kernels() -> bool:
    """The kernels on the chip, the ``jax.numpy`` recurrence on the CPU (where
    a test may still ask for the interpreted kernel by calling it)."""
    return not pallas_interpret()


def _column(row, width: int):
    """``row [1, width]`` -> ``[width, width]`` with ``row[i]`` along row
    ``i``: the row repeated down the sublanes, turned once."""
    return jnp.broadcast_to(row, (width, width)).T


# ----------------------------------------------------------------------------
# Decode
# ----------------------------------------------------------------------------


def _decode_kernel(li_ref, slot_ref, keep_ref, s_ref, q_ref, k_ref, v_ref,
                   d_ref, b_ref, o_ref, s_out_ref, *, heads: int):
    """One row, ``heads`` heads. ``s_ref [1, 1, heads, K, V]``; ``q``/``k``
    ``[1, 1, heads*K]``, ``v``/``o`` ``[1, 1, heads*V]`` with ``(h, lane)``
    on the lanes; ``d`` (the decay ``exp(g)``) and ``b`` (``beta``) ``[1, 1,
    heads*V]``, a head's number repeated over its lanes."""
    from jax.experimental import pallas as pl

    del li_ref, slot_ref
    keep = keep_ref[pl.program_id(0)] != 0
    K, V = s_ref.shape[-2:]
    for h in range(heads):
        kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        kcol = _column(k_ref[0, :, kl], K)  # [K, V]: k[i] along the lanes
        qcol = _column(q_ref[0, :, kl], K)
        s = jnp.where(keep, s_ref[0, 0, h], 0.0) * d_ref[0, :, vl]
        u = b_ref[0, :, vl] * (
            v_ref[0, :, vl] - jnp.sum(s * kcol, axis=0, keepdims=True))
        s = s + kcol * u
        s_out_ref[0, 0, h] = s
        o_ref[0, :, vl] = jnp.sum(s * qcol, axis=0, keepdims=True)


def gated_delta_decode(
    pool: jax.Array,  # [L, slots, H, K, V] float32, updated in place
    li,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    keep: jax.Array,  # [B] bool/int: 0 starts the row from zeros
    q: jax.Array,  # [B, H, K] normalised and scaled
    k: jax.Array,  # [B, H, K] normalised
    v: jax.Array,  # [B, H, V]
    g: jax.Array,  # [B, H] float32 log-decay
    beta: jax.Array,  # [B, H] float32
):
    """One position of every row on its own slot. Returns ``(o [B, H, V]
    float32, pool)``; the pool is the same buffer."""
    # Imported here: Pallas takes over a second to import, and every engine
    # start imports the model registry.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, H, K, V = pool.shape
    if K != V:
        raise ValueError(
            f"gated_delta_decode turns a key row into a [{K}, {K}] column "
            f"tile: value heads of {V} are not built")
    B = q.shape[0]
    hb = next(n for n in range(min(DECODE_HEADS, H), 0, -1) if H % n == 0)
    f32 = jnp.float32
    lanes = lambda x, w: jnp.repeat(  # noqa: E731
        x.astype(f32), w, axis=-1).reshape(B, 1, H * w)
    flat = lambda x: x.astype(f32).reshape(B, 1, -1)  # noqa: E731
    row = lambda w: pl.BlockSpec(  # noqa: E731
        (1, 1, hb * w), lambda b, h, li, sl, kp: (b, 0, h))
    state = pl.BlockSpec(
        (1, 1, hb, K, V), lambda b, h, li, sl, kp: (li[0], sl[b], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // hb),
        in_specs=[state, row(K), row(K), row(V), row(V), row(V)],
        out_specs=[row(V), state],
    )
    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, H * V), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the three prefetched scalars: the pool is input 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="gated_delta_decode",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      keep.astype(jnp.int32), pool, flat(q), flat(k), flat(v),
      lanes(jnp.exp(g.astype(f32)), V), lanes(beta, V))
    return o.reshape(B, H, V), pool


# ----------------------------------------------------------------------------
# Decode: the convolution's tail
# ----------------------------------------------------------------------------


def tail_shape(taps: int, channels: int) -> tuple:
    """A slot of the tails' pool: the last ``taps - 1`` rows of ``channels``
    numbers, each row folded to ``[channels / LANES, LANES]`` (one row where
    the channels are no whole lanes: :func:`conv_tail_decode` refuses that)."""
    lanes = LANES if channels % LANES == 0 else channels
    return (taps - 1, channels // lanes, lanes)


def conv_tail_reference(tails, li, slots, keep, true_len, x, w):
    """``x [B, T, C]``: the causal depthwise convolution over ``[tail |
    x]`` (``w [taps, C]``, oldest tap first) and each slot's tail as at its
    row's ``true_len``, in ``jax.numpy``: the prefill path, the CPU path and
    the tests' oracle for :func:`conv_tail_decode`. Returns ``(the sum [B,
    T, C] float32, tails)``."""
    B, T, C = x.shape
    n = w.shape[0] - 1
    f32 = jnp.float32
    tail = tails[li, slots].reshape(B, n, C)
    tail = jnp.where(keep[:, None, None] != 0, tail, jnp.zeros_like(tail))
    window = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    conv = sum(window[:, j:j + T].astype(f32) * w[j].astype(f32)
               for j in range(n + 1))
    # The tail at the row's true length: rows [len, len + n) of the window
    # are positions len - n .. len - 1.
    new_tail = jax.vmap(
        lambda win, at: jax.lax.dynamic_slice_in_dim(win, at, n, axis=0)
    )(window, true_len)
    return conv, tails.at[li, slots].set(
        new_tail.reshape((B,) + tails.shape[2:]))


def _conv_tail_kernel(li_ref, slot_ref, keep_ref, t_ref, x_ref, w_ref, o_ref,
                      t_out_ref):
    """One row. ``t_ref [1, 1, taps-1, R, LANES]`` the slot's tail, oldest
    row first; ``x_ref [1, R, LANES]`` this step's row; ``w_ref [taps, R,
    LANES]``; ``o_ref [1, R, LANES]`` float32."""
    from jax.experimental import pallas as pl

    del li_ref, slot_ref
    keep = keep_ref[pl.program_id(0)] != 0
    f32 = jnp.float32
    n = t_ref.shape[2]
    acc = None
    for j in range(n):
        # through float32: exact both ways, and a select the vector unit has
        t = jnp.where(keep, t_ref[0, 0, j].astype(f32), 0.0)
        if j:
            t_out_ref[0, 0, j - 1] = t.astype(t_out_ref.dtype)
        term = t * w_ref[j].astype(f32)
        acc = term if acc is None else acc + term
    x = x_ref[0]
    t_out_ref[0, 0, n - 1] = x.astype(t_out_ref.dtype)
    o_ref[0] = acc + x.astype(f32) * w_ref[n].astype(f32)


def conv_tail_decode(
    tails: jax.Array,  # [L, slots, taps-1, R, LANES], updated in place
    li,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    keep: jax.Array,  # [B] bool/int: 0 reads the row's tail as zeros
    x: jax.Array,  # [B, C] this step's row, C = R * LANES
    w: jax.Array,  # [taps, C] the depthwise kernel, oldest tap first
):
    """One position of every row: the convolution over ``[tail | x]`` and
    the tail shifted by ``x`` on the row's own slot. Returns ``(the sum [B,
    C] float32, tails)``; the pool is the same buffer. A row that is padding
    shifts the scratch slot's tail like any other."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, n, R, lanes = tails.shape
    B, C = x.shape
    if lanes != LANES or C != R * LANES or w.shape != (n + 1, C):
        raise ValueError(
            f"conv_tail_decode is built for rows of whole {LANES}-lane "
            f"tiles: tails {tails.shape}, row {x.shape}, kernel {w.shape}")
    row = pl.BlockSpec((1, R, LANES), lambda b, li, sl, kp: (b, 0, 0))
    tail = pl.BlockSpec(
        (1, 1, n, R, LANES), lambda b, li, sl, kp: (li[0], sl[b], 0, 0, 0))
    taps = pl.BlockSpec((n + 1, R, LANES), lambda b, li, sl, kp: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[tail, row, taps],
        out_specs=[row, tail],
    )
    conv, tails = pl.pallas_call(
        _conv_tail_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, R, LANES), jnp.float32),
            jax.ShapeDtypeStruct(tails.shape, tails.dtype),
        ],
        # operands count the three prefetched scalars: the pool is input 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=TAILS_VMEM_LIMIT_BYTES),
        interpret=pallas_interpret(),
        name="conv_tail_decode",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      keep.astype(jnp.int32), tails,
      jax.lax.optimization_barrier(x.astype(tails.dtype)).reshape(B, R, LANES),
      w.reshape(n + 1, R, LANES))
    return conv.reshape(B, C), tails


# ----------------------------------------------------------------------------
# Prefill
# ----------------------------------------------------------------------------


def _prefill_kernel(li_ref, slot_ref, keep_ref, len_ref, s_ref, q_ref, k_ref,
                    v_ref, g_ref, b_ref, o_ref, s_out_ref, s_scr, *,
                    chunk: int):
    """One row, one head, one chunk. ``s_ref [1, 1, 1, K, V]``; ``q``/``k``
    ``[1, chunk, K]``, ``v``/``o`` ``[1, chunk, V]``; ``g`` (the log-decay
    summed from the chunk's start) and ``b`` (``beta``) ``[1, 1, 1, 1,
    LANES]``, a chunk's numbers in the first ``chunk`` lanes; ``s_scr [K,
    V]`` carries the state from a chunk to the next."""
    from jax.experimental import pallas as pl

    del li_ref, slot_ref
    b, tc = pl.program_id(0), pl.program_id(2)
    C = chunk
    f32 = jnp.float32
    dot = functools.partial(
        jax.lax.dot_general, precision=_HI, preferred_element_type=f32)
    mm = lambda x, y: dot(x, y, (((1,), (0,)), ((), ())))  # noqa: E731
    mm_nt = lambda x, y: dot(x, y, (((1,), (1,)), ((), ())))  # noqa: E731

    @pl.when(tc == 0)
    def _start():
        s_scr[...] = jnp.where(keep_ref[b] != 0, s_ref[0, 0, 0], 0.0)

    real = len_ref[b] - tc * C  # positions of this chunk that hold a token

    @pl.when(real <= 0)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real > 0)
    def _chunk():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]  # [C, K] x2, [C, V]
        grow = g_ref[0, 0, 0]  # [1, LANES]
        gcol = _column(grow, LANES)[:C]  # [C, LANES]: g[i] along row i
        bcol = _column(b_ref[0, 0, 0], LANES)[:C]
        ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        # decay from position j to position i of the chunk, i >= j
        decay = jnp.exp(jnp.where(
            ii >= jj, gcol[:, :C] - grow[:, :C], -jnp.inf))
        kb, vb = k * bcol, v * bcol
        m = jnp.where(ii > jj, -(mm_nt(kb, k) * decay), 0.0)
        # (I - m)^-1 = (I + m)(I + m^2)(I + m^4)...: m^C = 0
        t = jnp.where(ii == jj, 1.0, m)
        p = m
        for _ in range(max(C.bit_length() - 2, 0)):
            p = mm(p, p)
            t = t + mm(t, p)
        eg = jnp.exp(gcol)  # [C, LANES]
        u = mm(t, vb)  # [C, V]
        w = mm(t, kb * eg)  # [C, K]
        s = s_scr[...]
        v_new = u - mm(w, s)
        o_ref[0] = mm(q * eg, s) + mm(mm_nt(q, k) * decay, v_new)
        last = gcol[C - 1:C]  # [1, LANES]: the chunk's whole log-decay
        k_end = k * jnp.exp(last - gcol)
        s_scr[...] = s * jnp.exp(last) + dot(
            k_end, v_new, (((0,), (0,)), ((), ())))

    s_out_ref[0, 0, 0] = s_scr[...]


def gated_delta_prefill(
    pool: jax.Array,  # [L, slots, H, K, V] float32, updated in place
    li,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    keep: jax.Array,  # [B] bool/int: 0 starts the row from zeros
    true_len: jax.Array,  # [B] int32: positions of the row that hold a token
    q: jax.Array,  # [B, T, H, K] normalised and scaled
    k: jax.Array,  # [B, T, H, K] normalised
    v: jax.Array,  # [B, T, H, V]
    g: jax.Array,  # [B, T, H] float32 log-decay; 0 at padding
    beta: jax.Array,  # [B, T, H] float32; 0 at padding
):
    """A chunk of positions a row, each row from its own slot's state and
    back to it. Returns ``(o [B, T, H, V] float32, pool)``; the pool is the
    same buffer, and a row's state is as at its true length."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, H, K, V = pool.shape
    B, T = q.shape[:2]
    f32 = jnp.float32
    if K != LANES or V != LANES:
        raise ValueError(
            f"gated_delta_prefill is built for {LANES}-wide key and value "
            f"heads, not {K} x {V}")
    C = CHUNK
    pad = -T % C
    nc = (T + pad) // C

    def seq(x, width):  # [B, T, H, width] -> [B, nc*C, H*width]
        x = x.astype(f32).reshape(B, T, H * width)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def per_chunk(x, cumulative):  # [B, T, H] -> [B, H, nc, 1, LANES]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0)))
        x = jnp.moveaxis(x, 1, 2).reshape(B, H, nc, C)
        if cumulative:
            x = jnp.cumsum(x, axis=-1)
        return jnp.pad(x, ((0, 0),) * 3 + ((0, LANES - C),))[:, :, :, None]

    idx = lambda f: (lambda b, h, t, li, sl, kp, ln: f(b, h, t, li, sl))  # noqa: E731
    rows = lambda w: pl.BlockSpec(  # noqa: E731
        (1, C, w), idx(lambda b, h, t, li, sl: (b, t, h)))
    nums = pl.BlockSpec(
        (1, 1, 1, 1, LANES), idx(lambda b, h, t, li, sl: (b, h, t, 0, 0)))
    state = pl.BlockSpec(
        (1, 1, 1, K, V), idx(lambda b, h, t, li, sl: (li[0], sl[b], h, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, H, nc),
        in_specs=[state, rows(K), rows(K), rows(V), nums, nums],
        out_specs=[rows(V), state],
        scratch_shapes=[pltpu.VMEM((K, V), f32)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_prefill_kernel, chunk=C),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nc * C, H * V), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the four prefetched scalars: the pool is input 4
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="gated_delta_prefill",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      keep.astype(jnp.int32), true_len.astype(jnp.int32), pool,
      seq(q, K), seq(k, K), seq(v, V), per_chunk(g, True),
      per_chunk(beta, False))
    return o[:, :T].reshape(B, T, H, V), pool
