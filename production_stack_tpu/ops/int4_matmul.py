"""Pallas TPU matmul over packed int4 weights with group-wise scales.

Why a kernel: the XLA formulation of int4 dequant (unpack nibbles →
stack/reshape → scale → dot) defeats operand fusion — XLA materializes the
dequantized bf16 weight matrix to HBM every step, which costs MORE
bandwidth than serving int8 and transiently allocates a full layer of bf16
weights (the OOM/latency cliff the 8B int4 smoke hit). int8 survives in
XLA because its dequant is a bare convert, which does fuse.

The kernel keeps the stream at the true 0.5 byte/weight: packed tiles DMA
from HBM once; the two nibble planes are derived in VMEM (arithmetic
shifts — no interleave/relayout, which Mosaic would hate); each group's
contribution is TWO MXU dots (even rows against the low plane, odd rows
against the high plane — the caller pre-splits x, so no reshuffle
anywhere), scaled per group POST-dot (a group's scale only varies along
the output axis, so it commutes with the contraction).

Layout contract (matches models/llama.py quantize_leaf_int4):
  x       [N, din]        activations (bf16/f32)
  packed  [din/2, dout]   int8, original row 2i in the low nibble of
                          packed row i, row 2i+1 in the high nibble
  scales  [G, dout]       f32, G = din/128 groups along the contraction
Returns [N, dout] f32.

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

from ..device import INTERPRET_ENV, pallas_interpret

GROUP = 128
# Groups folded into one grid step: 8 groups = 512 packed rows per DMA
# (256 KB at dout-tile 512) — deep enough to amortize per-cell overhead,
# small enough to double-buffer comfortably in VMEM.
GROUPS_PER_TILE = 8
IN_TILE = GROUP * GROUPS_PER_TILE  # original rows per grid step


def kernel_supports(din: int, dout: int, group: int) -> bool:
    return group == GROUP and din % IN_TILE == 0 and dout % 128 == 0


def use_int4_kernel(packed: jax.Array, scales: jax.Array) -> bool:
    """True when this (packed, scales) pair goes through the kernel: a
    supported 2-D shape, on ``tpu`` (compiled) or on the CPU with the
    interpret variable set. Tiny/odd shapes and MoE banks use the XLA
    dequant."""
    if packed.ndim != 2:
        return False
    din, dout = packed.shape[-2] * 2, packed.shape[-1]
    group = din // scales.shape[-2]
    if not kernel_supports(din, dout, group):
        return False
    return not pallas_interpret() or bool(os.environ.get(INTERPRET_ENV))


def _kernel(xe_ref, xo_ref, p_ref, s_ref, o_ref, *, groups: int):
    k = pl.program_id(2)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    half = GROUP // 2  # packed rows per group
    p = p_ref[...]  # [groups*half, tj] int8
    # Mosaic has no i8 vector shifts (arith.shli on vector<i8> fails to
    # legalize) — widen to i32, extract nibbles there. lo sign-extends the
    # low 4 bits via a 28-bit round trip; hi is a plain arithmetic shift
    # (p is already sign-extended by the i8→i32 convert).
    p32 = p.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28)
    hi = jnp.right_shift(p32, 4)
    xe = xe_ref[...]
    dt = xe.dtype
    # Packed row i holds original rows 2i/2i+1, both in group i // half —
    # ONE scale expansion (broadcast over the half rows of each group)
    # serves both planes, and each plane contracts in a single big MXU dot
    # (per-group dots were issue-latency-bound: 16 tiny [tn,64] dots per
    # cell cost ~20 µs of fixed overhead).
    s = s_ref[...].astype(dt)  # [groups, tj]
    s_exp = jnp.broadcast_to(
        s[:, None, :], (s.shape[0], half, s.shape[1])
    ).reshape(s.shape[0] * half, s.shape[1])
    # f32 activations ask for HIGHEST (exact) contraction — the op is
    # HBM-bound, so the extra MXU passes are free. bf16 must use the
    # native path (Mosaic rejects fp32 contract precision on bf16
    # operands: "Bad lhs type").
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    ge = jax.lax.dot_general(
        xe, lo.astype(dt) * s_exp, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )
    go = jax.lax.dot_general(
        xo_ref[...], hi.astype(dt) * s_exp, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )
    acc = acc + ge + go

    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k > 0)
    def _accum():
        o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("out_tile",))
def int4_matmul(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    out_tile: int = 512,
) -> jax.Array:
    """``x @ dequant(packed, scales)`` in fp32, streaming 0.5 B/weight."""
    N, din = x.shape
    dout = packed.shape[1]
    assert packed.shape[0] * 2 == din, (packed.shape, din)
    assert scales.shape == (din // GROUP, dout), scales.shape
    # Split even/odd contraction rows once (cheap XLA strided slices of the
    # small activation) so the kernel never reshuffles anything.
    xe = x[:, 0::2]
    xo = x[:, 1::2]
    tj = out_tile
    while dout % tj:
        tj //= 2
    # Row tile: pad N up to a sublane-friendly size.
    tn = 256 if N > 256 else max(8, 1 << (N - 1).bit_length())
    pad = -N % tn
    if pad:
        xe = jnp.pad(xe, ((0, pad), (0, 0)))
        xo = jnp.pad(xo, ((0, pad), (0, 0)))
    ni = (N + pad) // tn
    nj = dout // tj
    nk = din // IN_TILE
    half_tile = IN_TILE // 2  # packed rows per grid step

    out = pl.pallas_call(
        functools.partial(_kernel, groups=GROUPS_PER_TILE),
        grid=(ni, nj, nk),
        in_specs=[
            pl.BlockSpec((tn, half_tile), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, half_tile), lambda i, j, k: (i, k)),
            pl.BlockSpec((half_tile, tj), lambda i, j, k: (k, j)),
            pl.BlockSpec((GROUPS_PER_TILE, tj), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tn, tj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N + pad, dout), jnp.float32),
        interpret=pallas_interpret(),
    )(xe, xo, packed, scales)
    return out[:N] if pad else out
