"""Mamba-1's selective scan: the recurrence of a state-space layer whose
decay is a full ``[d_inner, d_state]`` matrix a position.

``s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) B_t^T`` and ``y_t = s_t C_t +
D * u_t`` with ``A = -exp(A_log)``: a channel ``d`` decays its ``N`` states
each at its own rate ``dt_t[d] * A[n, d]`` (Mamba-2's decay is one scalar a
head, which is why :mod:`production_stack_tpu.ops.ssm` cannot serve it). The
convolution, the projections and the gate are the model's; this module is
the recurrence alone, over a pool of per-sequence states.

The pool is ``[layers, slots, N, d_inner]`` float32: the ``N`` states on the
sublanes, the channels on the lanes, so that a position's ``dt`` and ``u``
rows spread down the sublanes and its ``B`` and ``C`` columns across the
lanes. Both kernels read a row's state by its slot through scalar prefetch
and write it back in place (``input_output_aliases``): no copy of the pool.

- :func:`selective_scan_decode` (``%selective_scan_decode``): one position a
  row; the whole ``[N, d_inner]`` state read and written once.
- :func:`selective_scan_prefill` (``%selective_scan_prefill``): a chunk of
  positions a row, each row from its own slot, ``LANES`` channels a grid
  cell with the state held in registers over the positions; positions at
  past the eight that hold a row's last token are not walked (their ``y``
  is zeros; inside those eight ``dt`` is 0 and the state stands).

:func:`scan_reference` is the same recurrence in ``jax.numpy``: the CPU
path, and the tests' oracle for the kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import pallas_interpret

# Channels a prefill grid cell walks (four lane tiles: the state's eight
# vregs and A's eight stay in registers over the positions), and positions a
# cell holds of dt, u, y, B and C.
LANES = 512
CHUNK = 256


def scan_reference(s0, u, dt, a_t, bm, cm, d):
    """``s0 [B, N, Di]`` float32, ``u``/``dt [B, T, Di]``, ``a_t [N, Di]``
    (``-exp(A_log)``), ``bm``/``cm [B, T, N]``, ``d [Di]`` -> ``(y [B, T,
    Di] float32, s_T [B, N, Di])``. A position whose ``dt`` is 0 leaves the
    state as it is."""
    f32 = jnp.float32

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp  # [B, Di], [B, Di], [B, N], [B, N]
        s = (jnp.exp(dt_t[:, None, :] * a_t) * s
             + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + d * u_t

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (u, dt, bm, cm))
    s, y = jax.lax.scan(step, s0.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), s


def use_kernels() -> bool:
    """The kernels on the chip, the ``jax.numpy`` form on the CPU (where a
    test may still ask for the interpreted kernel by calling it)."""
    return not pallas_interpret()


def _tile(width: int) -> int:
    return min(width, 128)


# ----------------------------------------------------------------------------
# Decode
# ----------------------------------------------------------------------------


def _decode_kernel(li_ref, slot_ref, keep_ref, s_ref, u_ref, dt_ref, a_ref,
                   b_ref, c_ref, d_ref, y_ref, s_out_ref, *, lane: int):
    """One row. ``s_ref [1, 1, N, Di]``; ``u``/``dt``/``y [1, 1, Di]``;
    ``a [N, Di]``; ``b``/``c [1, N, lane]`` (a column, the same in every
    lane); ``d [1, Di]``."""
    from jax.experimental import pallas as pl

    del li_ref, slot_ref
    keep = keep_ref[pl.program_id(0)] != 0
    bcol, ccol = b_ref[0], c_ref[0]
    for at in range(0, s_ref.shape[-1], lane):
        lanes = slice(at, at + lane)
        u, dt = u_ref[0, :, lanes], dt_ref[0, :, lanes]  # [1, lane]
        s = jnp.where(keep, s_ref[0, 0, :, lanes], 0.0)
        s = jnp.exp(dt * a_ref[:, lanes]) * s + (dt * u) * bcol
        s_out_ref[0, 0, :, lanes] = s
        y_ref[0, :, lanes] = (
            jnp.sum(s * ccol, axis=0, keepdims=True) + d_ref[:, lanes] * u)


def selective_scan_decode(
    pool: jax.Array,  # [L, slots, N, Di] float32, updated in place
    li,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    keep: jax.Array,  # [B] bool/int: 0 starts the row from zeros
    u: jax.Array,  # [B, Di]
    dt: jax.Array,  # [B, Di] float32 (after softplus)
    a_t: jax.Array,  # [N, Di] float32: -exp(A_log)
    bm: jax.Array,  # [B, N]
    cm: jax.Array,  # [B, N]
    d: jax.Array,  # [Di] float32
):
    """One position of every row on its own slot. Returns ``(y [B, Di]
    float32, pool)``; the pool is the same buffer."""
    # Imported here: Pallas takes over a second to import, and every engine
    # start imports the model registry.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, N, Di = pool.shape
    B = u.shape[0]
    lane = _tile(Di)
    f32 = jnp.float32
    col = lambda m: jnp.broadcast_to(  # noqa: E731
        m.astype(f32)[:, :, None], (B, N, lane))
    row = pl.BlockSpec((1, 1, Di), lambda b, li, sl, kp: (b, 0, 0))
    column = pl.BlockSpec((1, N, lane), lambda b, li, sl, kp: (b, 0, 0))
    state = pl.BlockSpec(
        (1, 1, N, Di), lambda b, li, sl, kp: (li[0], sl[b], 0, 0))
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda b, li, sl, kp: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[state, row, row, whole((N, Di)), column, column,
                  whole((1, Di))],
        out_specs=[row, state],
    )
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, lane=lane),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Di), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the three prefetched scalars: the pool is input 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name="selective_scan_decode",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      keep.astype(jnp.int32), pool, u.astype(f32)[:, None],
      dt.astype(f32)[:, None], a_t.astype(f32), col(bm), col(cm),
      d.astype(f32)[None])
    return y[:, 0], pool


# ----------------------------------------------------------------------------
# Prefill
# ----------------------------------------------------------------------------


def _prefill_kernel(li_ref, slot_ref, keep_ref, len_ref, s_ref, u_ref, dt_ref,
                    a_ref, b_ref, c_ref, d_ref, y_ref, s_out_ref, s_scr, *,
                    lane: int, chunk: int):
    """One row, one run of channels, one chunk of positions. ``s_ref [1, 1,
    N, W]``; ``u``/``dt``/``y [1, chunk, W]``; ``a [N, W]``; ``b``/``c [1,
    chunk, N]``; ``d [1, W]``; ``s_scr [N, W]`` carries the state from a
    chunk to the next."""
    from jax.experimental import pallas as pl

    del li_ref, slot_ref
    b, tc = pl.program_id(0), pl.program_id(2)
    W = s_ref.shape[-1]
    tiles = [slice(at, at + lane) for at in range(0, W, lane)]

    @pl.when(tc == 0)
    def _start():
        s_scr[...] = jnp.where(keep_ref[b] != 0, s_ref[0, 0], 0.0)

    # Positions of this chunk that hold a token: the rest is the bucket's
    # padding: past the last block of eight it is not walked and reads zeros.
    real = jnp.clip(len_ref[b] - tc * chunk, 0, chunk)

    @pl.when(real < chunk)
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(real > 0)
    def _walk():
        a = [a_ref[:, t] for t in tiles]
        dvec = [d_ref[:, t] for t in tiles]

        def eight(i, s):
            at = pl.multiple_of(i * 8, 8)
            u8 = u_ref[0, pl.ds(at, 8), :]
            dt8 = dt_ref[0, pl.ds(at, 8), :]
            # B and C of eight positions as columns: turned once.
            b8 = b_ref[0, pl.ds(at, 8), :].T  # [N, 8]
            c8 = c_ref[0, pl.ds(at, 8), :].T
            N = b8.shape[0]
            s = list(s)
            ys = [[] for _ in tiles]
            for j in range(8):
                bcol = jnp.broadcast_to(b8[:, j:j + 1], (N, lane))
                ccol = jnp.broadcast_to(c8[:, j:j + 1], (N, lane))
                for k, t in enumerate(tiles):
                    u, dt = u8[j:j + 1, t], dt8[j:j + 1, t]
                    s[k] = jnp.exp(dt * a[k]) * s[k] + (dt * u) * bcol
                    ys[k].append(
                        jnp.sum(s[k] * ccol, axis=0, keepdims=True)
                        + dvec[k] * u)
            for k, t in enumerate(tiles):
                y_ref[0, pl.ds(at, 8), t] = jnp.concatenate(ys[k], axis=0)
            return tuple(s)

        # Whole blocks of eight: dt is 0 at the padding inside the last one,
        # which leaves the state as it is.
        s = jax.lax.fori_loop(
            0, (real + 7) // 8, eight, tuple(s_scr[:, t] for t in tiles))
        for k, t in enumerate(tiles):
            s_scr[:, t] = s[k]

    s_out_ref[0, 0] = s_scr[...]


def selective_scan_prefill(
    pool: jax.Array,  # [L, slots, N, Di] float32, updated in place
    li,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    keep: jax.Array,  # [B] bool/int: 0 starts the row from zeros
    true_len: jax.Array,  # [B] int32: positions of the row that hold a token
    u: jax.Array,  # [B, T, Di]
    dt: jax.Array,  # [B, T, Di] float32 (after softplus; 0 at padding)
    a_t: jax.Array,  # [N, Di] float32: -exp(A_log)
    bm: jax.Array,  # [B, T, N]
    cm: jax.Array,  # [B, T, N]
    d: jax.Array,  # [Di] float32
):
    """A chunk of positions a row, each row from its own slot's state and
    back to it. Returns ``(y [B, T, Di] float32, pool)``; the pool is the
    same buffer, and a row's state is as at its true length."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, N, Di = pool.shape
    B, T, _ = u.shape
    f32 = jnp.float32
    W = min(LANES, Di)
    lane = _tile(Di)
    chunk = min(CHUNK, -(-T // 8) * 8)
    pad = -T % chunk
    if pad:
        grow = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        u, dt, bm, cm = grow(u), grow(dt), grow(bm), grow(cm)
    Tp = T + pad
    if Di % W:
        raise ValueError(f"d_inner {Di} is no multiple of {W} channels")
    idx = lambda f: (lambda b, c, t, li, sl, kp, ln: f(b, c, t, li, sl))  # noqa: E731
    seq = pl.BlockSpec((1, chunk, W), idx(lambda b, c, t, li, sl: (b, t, c)))
    col = pl.BlockSpec((1, chunk, N), idx(lambda b, c, t, li, sl: (b, t, 0)))
    state = pl.BlockSpec(
        (1, 1, N, W), idx(lambda b, c, t, li, sl: (li[0], sl[b], 0, c)))
    chan = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, W), idx(lambda b, c, t, li, sl: (0, c)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Di // W, Tp // chunk),
        in_specs=[state, seq, seq, chan(N), col, col, chan(1)],
        out_specs=[seq, state],
        scratch_shapes=[pltpu.VMEM((N, W), f32)],
    )
    y, pool = pl.pallas_call(
        functools.partial(_prefill_kernel, lane=lane, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Di), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the four prefetched scalars: the pool is input 4
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="selective_scan_prefill",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      keep.astype(jnp.int32), true_len.astype(jnp.int32), pool,
      u.astype(f32), dt.astype(f32), a_t.astype(f32), bm.astype(f32),
      cm.astype(f32), d.astype(f32)[None])
    return y[:, :T], pool
