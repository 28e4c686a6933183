"""Mamba-2 state-space mixer: the chunked scan for prefill and the in-place
state update for decode.

The recurrence, per head ``h`` (``P`` channels) with its group's ``B_t``,
``C_t`` (``N`` wide)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S in R^{P x N}
    y_t = S_t C_t                                       (+ D x_t, the caller's)

**Prefill** (:func:`ssd_chunked`) is the chunked (SSD) form in ``jax.numpy``:
inside a chunk the masked ``C B^T`` product, between chunks the state
recurrence, starting from a given state and returning the last one. A
position whose ``dt`` is 0 leaves the state as it was, which is how padded
positions are kept out of it.

**Decode** (:func:`ssm_decode`) is a Pallas kernel that reads each row's
state from the pool by slot index, applies one step and writes it back **in
place** (``input_output_aliases``): a pool is ``slots x 4 MiB`` a layer at
published widths and XLA's scatter would copy it every step.

The pool's layout is the kernel's: ``[layers, slots, H/hp, N, hp*P]`` float32,
``hp`` heads side by side on the lanes (:func:`pack_factor`; 2 at ``P`` = 64),
so that ``x`` and the decay, which arrive with ``(h, p)`` on the lanes, are
used as they come and only ``B`` and ``C`` (one row a group) are turned
through the transpose unit. :func:`pack_state` / :func:`unpack_state` go
between this and the plain ``[..., H, P, N]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..device import pallas_interpret

LANES = 128
# Groups of heads one grid step of ``ssm_decode`` takes (fewer where the model
# has fewer): at published widths 430.9 us a call at 2, 426.0 at 4 (chip
# microbenchmark, PR 31), so nothing asks for a knob.
GROUPS_PER_BLOCK = 2


def pack_factor(heads: int, head_dim: int, n_groups: int) -> int:
    """Heads that share one lane tile of the state: the largest power of two
    that fits ``LANES // head_dim`` and divides a group's heads."""
    per_group = heads // n_groups
    hp = 1
    while hp * 2 * head_dim <= LANES and per_group % (hp * 2) == 0:
        hp *= 2
    return hp


def packed_shape(heads: int, head_dim: int, state: int, n_groups: int):
    hp = pack_factor(heads, head_dim, n_groups)
    return (heads // hp, state, hp * head_dim)


def pack_state(s: jax.Array, n_groups: int) -> jax.Array:
    """``[..., H, P, N]`` -> ``[..., H/hp, N, hp*P]``."""
    *lead, H, P, N = s.shape
    hp = pack_factor(H, P, n_groups)
    s = s.reshape(*lead, H // hp, hp, P, N)
    s = jnp.moveaxis(s, -1, -3)  # [..., H/hp, N, hp, P]
    return s.reshape(*lead, H // hp, N, hp * P)


def unpack_state(s: jax.Array, head_dim: int) -> jax.Array:
    """``[..., H/hp, N, hp*P]`` -> ``[..., H, P, N]``."""
    *lead, Hq, N, W = s.shape
    hp = W // head_dim
    s = s.reshape(*lead, Hq, N, hp, head_dim)
    s = jnp.moveaxis(s, -3, -1)  # [..., H/hp, hp, P, N]
    return s.reshape(*lead, Hq * hp, head_dim, N)


# ----------------------------------------------------------------------------
# Prefill: chunked scan in jax.numpy
# ----------------------------------------------------------------------------


def _segsum(a: jax.Array) -> jax.Array:
    """``a [..., Q]`` -> ``[..., Q, Q]``: ``out[l, s] = sum_{s < k <= l} a[k]``
    for ``s <= l``, ``-inf`` above the diagonal."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B, T, H] float32, after softplus; 0 = leave the state
    a: jax.Array,  # [H] float32, negative
    bm: jax.Array,  # [B, T, G, N]
    cm: jax.Array,  # [B, T, G, N]
    s0: jax.Array,  # [B, H, P, N] float32
    chunk: int = 128,
):
    """The recurrence over ``T`` positions from state ``s0``. Returns
    ``(y [B, T, H, P] float32, s_T [B, H, P, N] float32)``."""
    B, T, H, P = x.shape
    G, N = bm.shape[-2:]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (T + pad) // Q
    rep = H // G
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt[..., None]).reshape(B, nc, Q, G, rep, P)
    bq = bm.astype(f32).reshape(B, nc, Q, G, N)
    cq = cm.astype(f32).reshape(B, nc, Q, G, N)
    da = (dt * a).reshape(B, nc, Q, G, rep)  # log-decay of each position
    da = jnp.moveaxis(da, 2, -1)  # [B, nc, G, rep, Q]
    cs = jnp.cumsum(da, axis=-1)

    # Within a chunk: y[l] += sum_{s <= l} exp(cs[l] - cs[s]) (C_l . B_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cq, bq, preferred_element_type=f32)
    m = cb[:, :, :, None] * jnp.exp(_segsum(da))  # [B, nc, G, rep, Q, Q]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xdt, preferred_element_type=f32)

    # What each chunk adds to the state at its end, and its whole decay.
    to_end = jnp.exp(cs[..., -1:] - cs)  # [B, nc, G, rep, Q]
    add = jnp.einsum(
        "bcgrs,bcsgrp,bcsgn->bcgrpn", to_end, xdt, bq,
        preferred_element_type=f32,
    )
    whole = jnp.exp(cs[..., -1])  # [B, nc, G, rep]

    def step(s, inp):
        add_c, whole_c = inp
        return whole_c[..., None, None] * s + add_c, s

    s_last, s_starts = jax.lax.scan(
        step, s0.astype(f32).reshape(B, G, rep, P, N),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)),
    )
    s_starts = jnp.moveaxis(s_starts, 0, 1)  # [B, nc, G, rep, P, N]
    # Across chunks: y[l] += exp(cs[l]) C_l . S_(chunk start)
    y = y + jnp.einsum(
        "bclgn,bcgrpn,bcgrl->bclgrp", cq, s_starts, jnp.exp(cs),
        preferred_element_type=f32,
    )
    y = y.reshape(B, nc * Q, H, P)[:, :T]
    return y, s_last.reshape(B, H, P, N)


def ssm_step(s, x, dt, a, bm, cm):
    """One position of the recurrence in ``jax.numpy`` (what the kernel
    computes): ``s [B, H, P, N]``, ``x [B, H, P]``, ``dt [B, H]``, ``a [H]``,
    ``bm``/``cm [B, G, N]`` -> ``(y [B, H, P], s)``."""
    H, G = x.shape[1], bm.shape[1]
    f32 = jnp.float32
    bh = jnp.repeat(bm.astype(f32), H // G, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm.astype(f32), H // G, axis=1)
    s = (
        jnp.exp(dt * a)[..., None, None] * s
        + (dt[..., None] * x.astype(f32))[..., None] * bh[:, :, None, :]
    )
    return jnp.einsum("bhpn,bhn->bhp", s, ch), s


# ----------------------------------------------------------------------------
# Decode: the in-place kernel
# ----------------------------------------------------------------------------


def _decode_kernel(li_ref, slot_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref,
                   y_ref, s_out_ref, *, tiles: int, groups: int, state: int):
    """One row, ``groups`` groups of ``tiles`` lane tiles each. ``s_ref``
    ``[1, 1, groups*tiles, N, W]``; ``decay``/``dtx``/``y`` ``[1, 1,
    groups*tiles*W]`` with ``(h, p)`` on the lanes; ``b``/``c`` ``[1, 1,
    groups*N]``."""
    del li_ref, slot_ref
    W = s_ref.shape[-1]
    for g in range(groups):
        # B and C of the group as columns: a row, repeated down the
        # sublanes, turned once.
        brow = b_ref[0, :, g * state:(g + 1) * state]  # [1, N]
        crow = c_ref[0, :, g * state:(g + 1) * state]
        bcol = jnp.broadcast_to(brow, (state, state)).T  # [N, N]: b[n] along lanes
        ccol = jnp.broadcast_to(crow, (state, state)).T
        if W != state:
            reps = -(-W // state)
            bcol = jnp.concatenate([bcol] * reps, axis=1)[:, :W]
            ccol = jnp.concatenate([ccol] * reps, axis=1)[:, :W]
        for t in range(tiles):
            i = g * tiles + t
            lanes = slice(i * W, (i + 1) * W)
            s = s_ref[0, 0, i]  # [N, W]
            s = decay_ref[0, :, lanes] * s + bcol * dtx_ref[0, :, lanes]
            s_out_ref[0, 0, i] = s
            y_ref[0, :, lanes] = jnp.sum(s * ccol, axis=0, keepdims=True)


def ssm_decode(
    pool: jax.Array,  # [L, slots, H/hp, N, hp*P] float32, updated in place
    li: jax.Array,  # scalar int32: the pool's layer
    slots: jax.Array,  # [B] int32: each row's slot
    decay: jax.Array,  # [B, H] float32: exp(dt A); 0 starts from zeros
    dtx: jax.Array,  # [B, H, P] float32: dt * x
    bm: jax.Array,  # [B, G, N] float32
    cm: jax.Array,  # [B, G, N] float32
    *,
    n_groups: int,
):
    """One decode step of every row on its own slot. Returns
    ``(y [B, H, P] float32, pool)``; the pool is the same buffer."""
    # Imported here: Pallas takes over a second to import, and every engine
    # start imports the model registry.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, Hq, N, W = pool.shape
    B, H, P = dtx.shape
    G = n_groups
    tiles = Hq // G  # lane tiles a group
    gb = math.gcd(G, GROUPS_PER_BLOCK)
    f32 = jnp.float32
    decay_l = jnp.repeat(decay.astype(f32), P, axis=-1).reshape(B, 1, H * P)
    dtx_l = dtx.astype(f32).reshape(B, 1, H * P)
    b_l = bm.astype(f32).reshape(B, 1, G * N)
    c_l = cm.astype(f32).reshape(B, 1, G * N)
    row = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, width), lambda b, g, li, sl: (b, 0, g))
    state = pl.BlockSpec(
        (1, 1, gb * tiles, N, W), lambda b, g, li, sl: (li[0], sl[b], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, G // gb),
        in_specs=[state, row(gb * tiles * W), row(gb * tiles * W),
                  row(gb * N), row(gb * N)],
        out_specs=[row(gb * tiles * W), state],
    )
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, tiles=tiles, groups=gb, state=N),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, H * P), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the two prefetched scalars: the pool is input 2
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=pallas_interpret(),
        name="ssm_decode",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      pool, decay_l, dtx_l, b_l, c_l)
    return y.reshape(B, H, P), pool
