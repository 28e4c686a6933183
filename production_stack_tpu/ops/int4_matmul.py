"""Pallas TPU matmul over packed int4 weights with group-wise scales.

Why a kernel: the XLA formulation of int4 dequant (unpack nibbles →
stack/reshape → scale → dot) defeats operand fusion — XLA materializes the
dequantized bf16 weight matrix to HBM every step, which costs MORE
bandwidth than serving int8 and transiently allocates a full layer of bf16
weights (the OOM/latency cliff the 8B int4 smoke hit). int8 survives in
XLA because its dequant is a bare convert, which does fuse.

The kernel keeps the stream at the true 0.5 byte/weight, and at decode
widths it runs at the speed of its weight DMAs (PERF.md §6, PR 28):

* **Row-contiguous weight tiles.** A weight block is ``(tk packed rows,
  tc columns)`` with ``tc`` the whole output width while the row tile is
  small (``tn`` ≤ 64: the ``[tn, dout]`` f32 output block stays resident
  over the contraction, the grid's inner axis), so one block is ONE
  contiguous run of HBM; ``tk`` is the largest of 512/256/128 packed rows
  that keeps the block within 1.75 MiB (128 x 14336, 256 x 4096, 512 x
  1024 at the 7B/8B widths: 16-56 grid steps a call). Wider row tiles
  (prefill) take column tiles of at most 2048 so the output block stays
  2 MiB. The scales ride as ``(8 groups, tc)`` blocks whose index moves
  every ``8 // groups-per-step`` steps.
* **A grid step walks its 2-8 groups, unrolled, each at the tile's whole
  width**: unpack → one dot → scale, accumulated into the step's ``[tn,
  tc]`` f32 partial, which is added to the output block once. Straight-line
  code that Mosaic cuts into vregs and schedules itself; the MXU's latency
  overlaps with the next group's unpack. Walking column chunks inside a
  group bought nothing on the chip (the same times from 128 columns to the
  whole width, 16 to 1,024 rows) and cost dearly off it: a rolled chunk
  loop waited out the MXU's latency every iteration (80 against 44 us for
  4096 -> 14336 at 16 rows; 30 % slower at 256 rows), and an unrolled one
  made every step program's kernels 10x the equations to trace and lower,
  1-2 s more of every step shape's first use in a process, which the
  compile cache does not save (PERF.md §6, PR 28).
* **Nibbles become bf16 by integer ops on the packed words, no convert.**
  Four packed bytes of one column (packed rows 4i..4i+3) are one 32-bit
  word holding eight nibbles, group rows 8i..8i+7. For a in 0..3,
  ``((w >> (4a - 3)) & 0x00780078) ^ 0x41C041C0`` is a pair of bf16 bit
  patterns: exponent 2^4, the nibble in the top four mantissa bits with its
  sign bit flipped, i.e. the value ``24 + s`` for the signed nibble ``s``
  (exact), rows 8i + a (low half) and 8i + a + 4 (high half). Three integer
  ops per 16 x 128 weights instead of widen, two shifts, two converts and
  a multiply per 8 x 128.
* **Scale (and the +24) after the dot.** A group's scale varies along the
  output axis only, so it commutes with the contraction: each group is one
  128-deep MXU dot of the unscaled planes, and the ``[tn, tc]`` f32 partial
  is corrected and scaled on the way into the accumulator,
  ``(dot - 24 * rowsum(x_g)) * scale_g``, in f32 (the scale is no longer
  rounded to the activation dtype). That costs ``tn x tc`` per group where
  scaling the weights costs ``128 x tc``; at 256 rows the MXU's own time
  hides either, so one rule served every row count measured (1 to 1,024
  rows, faster than the scaled-weights body at each: PERF.md §6) and there
  is no second body.
* **The activations arrive as the contraction's two contiguous halves.**
  The caller-side split is ``xe = x[:, :din // 2]``, ``xo = x[:, din //
  2:]``: unit-stride, 128-lane aligned slices that XLA folds into whatever
  produced ``x`` (the split into even and odd columns that they replace was
  a lane-strided gather before every call: 11.9-12.9 us for each
  ``bf16[7168, 16]`` half of ``w_down``'s input at 16 rows, and with the
  layout copies XLA put around the gathers 1.9 ms of an 18.3 ms decode
  step: PERF.md §6, PR 37). A weight block is original rows
  ``[2 k tk, 2 (k + 1) tk)``, so a grid step needs ``2 tk`` adjacent columns
  of ``x``, which lie wholly in ``xe`` for the first half of the steps and in
  ``xo`` for the second (``_tiles`` keeps ``2 tk`` a divisor of ``din / 2``).
  Both halves ride as ``(tn, 2 tk)`` blocks; the one a step does not read
  keeps its block index and is not fetched again, and the step selects the
  live block. Plane ``a`` holds a group's rows ``a, a + 4, a + 8, ...``, so
  the kernel reorders a group's 128 adjacent lanes to the planes' K order
  with one 128 x 128 0/1 matrix on the MXU (exact: every output is one input
  times 1.0), once per group and grid step — against the 8-112 weight tiles
  the group's own dot pushes. (A split in the planes' order made by XLA, a
  reshape to ``[..., 32, 4]``, cost a prefill step more than the kernel
  saved: minor dimensions of 4 and 32 pad to 128 lanes.)

VMEM: the blocks are double-buffered by the pipeline — 2 x (weights ≤ 1.75
MiB + scales 8 x tc x 4 + two x blocks of ``tn x 2 tk``, ≤ 0.5 MiB each) +
2 x the f32 output block (≤ 3.5 MiB) — at most 14 MiB at the widths served.
``vmem_limit_bytes`` is 112 of the 128 MiB all the same, to leave XLA no
room to stage a layer stack of scales on chip around every call (see
``VMEM_LIMIT_BYTES``).

Layout contract (matches models/llama.py quantize_leaf_int4):
  x       [N, din]        activations (bf16/f32)
  packed  [din/2, dout]   int8, original row 2i in the low nibble of
                          packed row i, row 2i+1 in the high nibble
  scales  [G, dout]       f32, G = din/128 groups along the contraction
Returns [N, dout] f32. f32 activations (the CPU tests) take the same walk
with the planes widened to f32, the 24 taken off there, and a HIGHEST dot.

The weights are never sliced: the model keeps every layer's matrix stacked
on a leading axis, and :func:`int4_matmul_stacked` takes (packed
``[L, din/2, dout]``, scales ``[L, G, dout]``, layer) and picks the layer in
its block index maps (scalar prefetch), as the paged-attention kernels take
(cache, layer). A Mosaic call cannot take a ``dynamic-slice`` as a fused
operand the way an XLA dot can, so handing it ``packed[li]`` would make XLA
copy each layer's slice to a fresh buffer before every call — all of the
packed weights copied once per step. :func:`int4_matmul` is the same kernel
body and the same tiles without the layer operand (custom call
``int4_matmul``), for a caller that holds a single ``[din/2, dout]`` matrix.

Constraints: group size 128, din % 1024 == 0, dout % 128 == 0 — all real
checkpoint shapes (8B: 4096/14336/1024 contractions) qualify; tiny debug
shapes use the XLA dequant in the caller.

Selection (:func:`use_int4_kernel`) is by platform and shape only: on
``tpu`` every supported shape goes through the compiled kernel; on the CPU
the XLA dequant serves, unless ``PST_FORCE_PALLAS_INTERPRET`` asks for the
kernel interpreted (the tests do). A Mosaic kernel cannot be partitioned
by GSPMD and this one has no per-shard wrapper, so the runner refuses
``int4`` on a mesh of more than one device on ``tpu``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import INTERPRET_ENV, pallas_interpret

GROUP = 128
HALF = GROUP // 2  # packed rows per group
# din is a multiple of this, so that a packed-row tile of 128 or 256 divides
# din/4, the packed rows under one half of x (512 does where din is a
# multiple of 2048: ``_tiles``), and the groups come in whole (8, tc) scale
# blocks.
IN_ALIGN = 1024
WEIGHT_BLOCK_BYTES = 128 * 14336  # 1.75 MiB: the largest weight block
WIDE_ROWS = 64  # row tiles up to this keep the whole output width resident
WIDE_TILE = 2048  # the column tile of wider row tiles
# The call's VMEM scope, of the v5e's 128 MiB. The double-buffered blocks need
# at most 14 MiB (module docstring); the scope is set far above that because
# what it leaves is what XLA's memory-space assignment may keep on chip
# during the call, and with more it prefetched a whole [L, G, dout] f32 stack
# of scales (58 MB for 14336 -> 4096) before every layer's call, which reads
# 1/L of it: 0.95 ms of every decode step (PERF.md §6, PR 28). Pinning the
# operand to HBM does not stop a prefetch; leaving it no room does.
VMEM_LIMIT_BYTES = 112 << 20
# A plane's bf16 pair: the nibble sits in mantissa bits 3..6 of each half,
# exponent 2**4, sign bit of the nibble flipped -> the value NIBBLE_BIAS + s.
PLANE_MASK = 0x00780078
PLANE_MAGIC = 0x41C041C0
NIBBLE_BIAS = 24.0


def kernel_supports(din: int, dout: int, group: int) -> bool:
    return group == GROUP and din % IN_ALIGN == 0 and dout % 128 == 0


def use_int4_kernel(packed: jax.Array, scales: jax.Array) -> bool:
    """True when one layer's (packed, scales) pair goes through the kernel:
    a supported 2-D shape, on ``tpu`` (compiled) or on the CPU with the
    interpret variable set. Tiny/odd shapes and MoE banks use the XLA
    dequant. Takes per-layer shapes (arrays or ``ShapeDtypeStruct``): a
    stacked dense leaf ``[L, din/2, dout]`` has the rank of one layer's bank
    ``[E, din/2, dout]``, so strip the layer axis before asking."""
    if packed.ndim != 2:
        return False
    din, dout = packed.shape[-2] * 2, packed.shape[-1]
    group = din // scales.shape[-2]
    if not kernel_supports(din, dout, group):
        return False
    return not pallas_interpret() or bool(os.environ.get(INTERPRET_ENV))


def _planes(w8: jax.Array) -> jax.Array:
    """One group's packed ``[64, tc]`` int8 -> ``[128, tc]`` bf16 holding
    ``NIBBLE_BIAS + s``: four planes of 32 rows, row ``2i + h`` of plane
    ``a`` the group's row ``8i + a + 4h`` = ``4 (2i + h) + a``."""
    w32 = pltpu.bitcast(w8, jnp.int32)  # [16, tc], eight nibbles a word
    planes = []
    for a in range(4):
        sh = 4 * a - 3
        t = jnp.left_shift(w32, -sh) if sh < 0 else jnp.right_shift(w32, sh)
        t = jnp.bitwise_xor(jnp.bitwise_and(t, PLANE_MASK), PLANE_MAGIC)
        planes.append(pltpu.bitcast(t, jnp.bfloat16))  # [32, tc]
    return jnp.concatenate(planes, axis=0)


def _kernel(*refs, gps: int, spb: int, half_steps: int):
    # A stacked call has the layer in front (scalar-prefetched); only the
    # index maps read it: p_ref / s_ref are already that layer's tile, the
    # layer axis squeezed.
    xe_ref, xo_ref, p_ref, s_ref, o_ref = refs[-5:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dt = xe_ref.dtype
    # f32 activations ask for HIGHEST (exact) contraction, on f32 planes
    # with the bias already off. bf16 must use the native path (Mosaic
    # rejects fp32 contract precision on bf16 operands: "Bad lhs type").
    exact = dt == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else None
    # The step's 2 tk adjacent columns of x lie in the lower half of the
    # contraction for the first half of the steps and in the upper half after
    # (the block of the other half stands still meanwhile: ``_call``).
    x_blk = jnp.where(k < half_steps, xe_ref[...], xo_ref[...])
    # Lane c = 32 a + j of a group's operand is the group's column 4 j + a.
    lane = jax.lax.broadcasted_iota(jnp.int32, (GROUP, GROUP), 1)
    src = ((lane & 31) << 2) + (lane >> 5)
    perm = (jax.lax.broadcasted_iota(jnp.int32, (GROUP, GROUP), 0)
            == src).astype(dt)
    xs, biases = [], []
    for g in range(gps):
        xg = jax.lax.dot_general(
            x_blk[:, g * GROUP:(g + 1) * GROUP], perm,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec,
        )
        xs.append(xg.astype(dt))
        biases.append(None if exact else NIBBLE_BIAS * jnp.sum(
            xg, axis=1, keepdims=True))
    # The scales block holds 8 groups; this step's are gps rows of it.
    row0 = (k % spb) * gps

    acc = None
    for g in range(gps):
        w = _planes(p_ref[g * HALF:(g + 1) * HALF, :])
        if exact:
            d = jax.lax.dot_general(
                xs[g], w.astype(jnp.float32) - NIBBLE_BIAS,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
        else:
            d = jax.lax.dot_general(
                xs[g], w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) - biases[g]
        d = d * s_ref[pl.ds(row0 + g, 1), :]
        acc = d if acc is None else acc + d
    o_ref[...] += acc


def _tiles(n: int, din: int, dout: int) -> tuple[int, int, int]:
    """(tn, tc, tk) from the call's shape alone."""
    # Row tile: pad N up to a sublane-friendly size.
    tn = 256 if n > 256 else max(8, 1 << (n - 1).bit_length())
    tc = dout
    if tn > WIDE_ROWS:
        tc = max(c for c in range(128, WIDE_TILE + 1, 128) if dout % c == 0)
    # A step's 2 tk columns of x must lie in one half of the contraction:
    # 2 tk divides din / 2 (512 packed rows only for an even multiple of 1024).
    tk = next(
        (t for t in (512, 256)
         if t * tc <= WEIGHT_BLOCK_BYTES and din % (4 * t) == 0), 128)
    return tn, tc, tk


def _call(x, packed, scales, li):
    """The one ``pallas_call``: ``li`` None for a 2-D ``packed [din/2,
    dout]``, else the layer of a stacked ``packed [L, din/2, dout]``. Same
    tiles, same order either way; the layer only offsets the weight DMAs."""
    stacked = li is not None
    N, din = x.shape
    dout = packed.shape[-1]
    assert packed.shape[-2] * 2 == din, (packed.shape, din)
    assert scales.shape == packed.shape[:-2] + (din // GROUP, dout), scales.shape
    # The weights stream from HBM, which is what the roofline is counted
    # against. Left to itself XLA stages the scan-sliced 2-D matrix (the one
    # leaf of models/llama.py SLICED_KERNEL_INT4) in its faster on-chip space,
    # and this kernel, bound by its DMAs, then reads it 2x faster than HBM
    # can be read (2.5 against 4.9 us for 4096 x 1024: 118 % of the HBM
    # roofline; PERF.md §6, PR 28). (The interpreter knows no memory spaces.)
    interpret = pallas_interpret()
    if not interpret:
        packed = pltpu.with_memory_space_constraint(packed, pltpu.HBM)
    tn, tc, tk = _tiles(N, din, dout)
    pad = -N % tn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    # The contraction's lower and upper half, under the names and shapes the
    # benchmark's cost functions read (two ``[N, din/2]`` operands). Step k's
    # weight block is original rows [2 k tk, 2 (k + 1) tk): adjacent columns
    # of x, all in ``xe`` for the first half of the steps and all in ``xo``
    # after. Unit-stride slices cost next to nothing (one two-output fusion
    # a distinct x, 0.03-0.11 us at 16 rows); the split into even and odd
    # columns they replace was a lane-strided gather before every call,
    # 11.9-12.9 us for each ``bf16[7168, 16]`` half of down's input, 1.9 ms
    # of an 18.3 ms decode step with its layout copies (PERF.md §6, PR 37).
    xe = x[:, :din // 2]
    xo = x[:, din // 2:]
    gps = tk // HALF  # groups per grid step
    spb = 8 // gps  # grid steps per (8, tc) scales block
    nk = din // 2 // tk
    half_steps = nk // 2
    grid = ((N + pad) // tn, dout // tc, nk)

    # Index maps get the grid position, then the prefetched scalars (the
    # layer, when stacked), which lead the weight and scale block indices.
    lead = (None,) if stacked else ()
    scalars = (jnp.asarray(li, jnp.int32).reshape(1),) if stacked else ()
    layer = lambda refs: tuple(r[0] for r in refs)
    w_map = lambda i, j, k, *refs: layer(refs) + (k, j)
    s_map = lambda i, j, k, *refs: layer(refs) + (k // spb, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            # (tn, 2 tk) blocks of each half; the one the step does not read
            # keeps its index, and a block whose index stands is not fetched
            # again: x is read once a column tile, as before.
            pl.BlockSpec((tn, 2 * tk), lambda i, j, k, *_: (
                i, jnp.minimum(k, half_steps - 1))),
            pl.BlockSpec((tn, 2 * tk), lambda i, j, k, *_: (
                i, jnp.maximum(k - half_steps, 0))),
            pl.BlockSpec(lead + (tk, tc), w_map),
            pl.BlockSpec(lead + (8, tc), s_map),
        ],
        out_specs=pl.BlockSpec((tn, tc), lambda i, j, k, *_: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, gps=gps, spb=spb, half_steps=half_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N + pad, dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        # The device trace names the custom call by this.
        name="int4_matmul_stacked" if stacked else "int4_matmul",
    )(*scalars, xe, xo, packed, scales)
    return out[:N] if pad else out


@jax.jit
def int4_matmul_stacked(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    li,
) -> jax.Array:
    """``x @ dequant(packed[li], scales[li])`` in fp32, streaming 0.5
    B/weight, with ``packed [L, din/2, dout]`` and ``scales [L, G, dout]``
    read in place: ``li`` (int32 scalar, may be traced — the model's layer
    scan) only offsets the tiles' DMAs."""
    assert packed.ndim == 3, packed.shape
    return _call(x, packed, scales, li)


@jax.jit
def int4_matmul(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
) -> jax.Array:
    """``x @ dequant(packed, scales)`` in fp32 for a single ``[din/2, dout]``
    matrix, streaming 0.5 B/weight."""
    assert packed.ndim == 2, packed.shape
    return _call(x, packed, scales, None)
