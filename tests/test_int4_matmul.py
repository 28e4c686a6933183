"""Pallas int4-matmul kernel: exactness vs f64 numpy truth, and the
model-level wiring that routes serving-shape int4 matmuls through it.

The XLA int4 dequant materializes bf16 weights per layer (no operand
fusion through the unpack); the kernel streams 0.5 byte/weight. See
ops/int4_matmul.py.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models.llama import (
    QUANT4_SUFFIX,
    Llama,
    StackedInt4,
    _layer_params,
    quantize_leaf_int4,
    quantize_tree,
    SLICED_KERNEL_INT4,
    split_kernel_int4,
)
from production_stack_tpu.models.registry import get_model_config
from production_stack_tpu.ops.int4_matmul import (
    _tiles,
    int4_matmul,
    int4_matmul_stacked,
    kernel_supports,
    use_int4_kernel,
)

pytestmark = pytest.mark.fast


def _truth(x, packed, scales):
    pk, sc = np.asarray(packed), np.asarray(scales, np.float64)
    din, dout = pk.shape[0] * 2, pk.shape[1]
    lo = ((pk.astype(np.int8) << 4) >> 4).astype(np.float64)
    hi = (pk.astype(np.int8) >> 4).astype(np.float64)
    w = np.empty((din, dout))
    w[0::2], w[1::2] = lo, hi
    g = din // sc.shape[0]
    w = (w.reshape(-1, g, dout) * sc[:, None, :]).reshape(din, dout)
    return np.asarray(x, np.float64) @ w


# (din, dout, N, layer): layer None is the 2-D entry, else the stacked entry
# on three layers' weights. Each new case crosses a boundary of the kernel's
# tile rule (ops/int4_matmul.py ``_tiles``): grid steps along the contraction
# (din/2 over tk = 512/256/128 packed rows, by the tile's width), scale
# blocks shared by 2 or 4 steps, widths that are no multiple of 256 or 512,
# the whole output width up to 64 rows and column tiles above, padded row
# tiles. The activations go in as the contraction's lower and upper half and
# a step reads its 2 tk columns from one of them: the first half of the steps
# from ``xe``, the rest from ``xo``, so every case walks both, from one step
# each (1024) to 28 each (14336 at tk 128).
TRUTH_CASES = [
    (1024, 256, 5, None),
    (2048, 512, 64, None),
    (1024, 128, 1, None),
    (2048, 384, 16, None),  # two steps of 512 packed rows; 384 columns
    (1024, 640, 17, None),  # two steps of 256; 640 columns; 17 rows pad to 32
    (1024, 4096, 16, None),  # tk 256: a scales block serves two steps
    (1024, 7296, 1, None),  # tk 128: four steps a scales block
    (1024, 4096, 65, None),  # over 64 rows: two column tiles of 2048
    (2048, 2560, 300, None),  # two row tiles of 256, column tiles of 1280
    (2048, 512, 16, 2),  # stacked, layer 2 of 3
    (1024, 4096, 65, 1),  # stacked, column tiles, layer 1 of 3
    # An odd multiple of 1024: 512 packed rows would straddle the halves
    # (1536 = 3 x 512 columns each), so the rule gives 256 and six steps.
    (3072, 1024, 16, None),
    (3072, 4096, 1, None),
    (3072, 1024, 200, None),  # 200 rows pad to one tile of 256
    (3072, 4096, 256, 2),  # stacked; two column tiles re-read both halves
    # The served contractions, 4096 and 14336, at every tk.
    (4096, 1024, 1, None),  # tk 512: two steps a half
    (4096, 1024, 256, None),
    (4096, 4096, 16, 1),  # stacked; tk 256: four steps a half
    (4096, 4096, 200, None),  # padded, column tiles of 2048 at tk 512
    (4096, 14336, 16, None),  # tk 128: eight steps a half, gate / up
    (2048, 14336, 200, None),  # seven column tiles over one step a half
    (14336, 1024, 16, None),  # tk 512: seven steps a half
    (14336, 4096, 1, None),  # tk 256: fourteen steps a half, down
    (14336, 4096, 256, None),  # prefill: tk 512, two column tiles
]


@pytest.mark.parametrize(
    "din,dout,N,layer", TRUTH_CASES,
    ids=[f"{d}x{o}-n{n}" + ("" if li is None else f"-layer{li}")
         for d, o, n, li in TRUTH_CASES],
)
def test_kernel_matches_f64_truth(din, dout, N, layer):
    rng = np.random.default_rng(din + N)
    lead = () if layer is None else (3,)
    w = jnp.asarray(
        rng.normal(size=lead + (din, dout)).astype(np.float32) * 0.02
    )
    packed, scales = quantize_leaf_int4(w)
    x = jnp.asarray(rng.normal(size=(N, din)).astype(np.float32))
    if layer is None:
        got = np.asarray(int4_matmul(x, packed, scales))
    else:
        got = np.asarray(
            int4_matmul_stacked(x, packed, scales, jnp.int32(layer))
        )
        packed, scales = packed[layer], scales[layer]
    ref = _truth(x, packed, scales)
    assert got.shape == ref.shape
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert err < 1e-5, err


@pytest.mark.parametrize("N", [16, 65], ids=["decode16", "prefill65"])
def test_bf16_kernel_matches_dequant_f32_dot(N):
    """bf16 activations, the path the chip serves: exact nibbles (carried as
    bf16 ``24 + s``, the 24 taken off the f32 partial), the group's lanes
    reordered by a 0/1 matrix, the scale applied in f32, against the f32
    dequant and an exact dot."""
    from production_stack_tpu.models.llama import dequant_int4

    rng = np.random.default_rng(N)
    w = jnp.asarray(rng.normal(size=(2048, 640)).astype(np.float32) * 0.02)
    packed, scales = quantize_leaf_int4(w)
    x = jnp.asarray(rng.normal(size=(N, 2048)), jnp.bfloat16)
    got = np.asarray(int4_matmul(x, packed, scales))
    ref = np.asarray(x.astype(jnp.float32), np.float64) @ np.asarray(
        dequant_int4(packed, scales, jnp.float32), np.float64
    )
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert err < 1e-4, err


# (rows, din, dout) -> (tn, tc, tk). The first six are the calls the dense
# cell makes (16-row decode, 128-256-row prefill) and keep the tiles PR 28
# chose; the rest are contractions whose half is an odd number of 512-row
# steps.
TILE_CASES = [
    ((16, 4096, 14336), (16, 14336, 128)),
    ((16, 14336, 4096), (16, 4096, 256)),
    ((16, 4096, 4096), (16, 4096, 256)),
    ((16, 4096, 1024), (16, 1024, 512)),
    ((256, 4096, 14336), (256, 2048, 512)),
    ((200, 14336, 4096), (256, 2048, 512)),
    ((16, 1024, 1024), (16, 1024, 256)),
    ((16, 3072, 1024), (16, 1024, 256)),
    ((300, 5120, 2048), (256, 2048, 256)),
    ((1, 3072, 14336), (8, 14336, 128)),
]


@pytest.mark.parametrize(
    "shape,tiles", TILE_CASES, ids=["x".join(map(str, c)) for c, _ in TILE_CASES]
)
def test_tiles_keep_a_step_inside_one_half(shape, tiles):
    """A grid step reads ``2 tk`` adjacent columns of ``x``, which must lie
    wholly in the lower or in the upper half of the contraction: an even
    number of steps, half of them in each."""
    n, din, dout = shape
    assert _tiles(n, din, dout) == tiles
    tk = tiles[2]
    assert (din // 2) % (2 * tk) == 0
    assert (din // 2 // tk) % 2 == 0


def test_kernel_support_gate():
    assert kernel_supports(4096, 14336, 128)
    assert kernel_supports(1024, 128, 128)
    assert not kernel_supports(512, 128, 128)  # din below 1024
    assert not kernel_supports(4096, 100, 128)  # ragged dout
    assert not kernel_supports(128, 128, 64)  # tiny-model fallback group


STACK_L = 4


@functools.lru_cache(maxsize=None)
def _stack(din, dout):
    """Four layers of different weights: a wrong layer offset cannot pass."""
    rng = np.random.default_rng(7)
    w = jnp.asarray(
        rng.normal(size=(STACK_L, din, dout)).astype(np.float32) * 0.02
    )
    packed, scales = quantize_leaf_int4(w)
    assert not np.array_equal(np.asarray(packed[0]), np.asarray(packed[1]))
    return packed, scales


# (din, dout, N, li). The first six are one step a half at tk 512; then an odd
# multiple of 1024 (six steps of 256) and 4096 -> 4096 (eight steps of 256 up
# to 64 rows, four of 512 under column tiles above), at one row, a decode
# batch, a padded row tile and a whole one.
STACK_CASES = (
    [(2048, 512, N, li) for N in (16, 300) for li in (0, 1, STACK_L - 1)]
    + [(3072, 1024, N, 2) for N in (1, 16, 200, 256)]
    + [(4096, 4096, N, 1) for N in (1, 16, 200, 256)]
)


@pytest.mark.parametrize(
    "din,dout,N,li", STACK_CASES,
    ids=[f"{d}x{o}-n{n}-layer{li}" for d, o, n, li in STACK_CASES],
)
def test_stacked_equals_2d_kernel_exactly(din, dout, N, li):
    """The stacked call at layer ``li`` (traced, as the layer scan hands it
    over) is the 2-D call on that layer's slice, bit for bit: same tiles,
    same order, only the DMA's layer offset differs."""
    packed, scales = _stack(din, dout)
    rng = np.random.default_rng(N + li)
    x = jnp.asarray(rng.normal(size=(N, din)), jnp.bfloat16)
    want = np.asarray(int4_matmul(x, packed[li], scales[li]))
    got = np.asarray(
        jax.jit(int4_matmul_stacked)(x, packed, scales, jnp.int32(li))
    )
    assert got.shape == (N, dout)
    assert np.array_equal(got, want)
    other = np.asarray(int4_matmul(x, packed[(li + 1) % STACK_L],
                                   scales[(li + 1) % STACK_L]))
    assert not np.array_equal(got, other)


def _eligible_model(**over):
    cfg = dataclasses.replace(
        get_model_config(over.pop("preset", "tiny-llama-debug")),
        hidden_size=1024,
        intermediate_size=1024,
        num_heads=8,
        num_kv_heads=8,
        head_dim=128,
        num_layers=2,
        dtype="float32",
        **over,
    )
    model = Llama(cfg)
    params = quantize_tree(
        model.init_params(jax.random.PRNGKey(0)), mode="int4"
    )
    return model, params


def _forward_args(model, T=8, nb=4, bs=8):
    rng = np.random.default_rng(1)
    return (
        jnp.asarray(rng.integers(1, 500, size=(1, T)), jnp.int32),
        jnp.arange(T, dtype=jnp.int32)[None],
        jnp.arange(T, dtype=jnp.int32)[None],
        jnp.arange(nb, dtype=jnp.int32)[None],
        jnp.full((1,), T, jnp.int32),
        jnp.full((1,), T - 1, jnp.int32),
        model.make_kv_cache(nb, bs),
    )


def _layer_scans(jaxpr, n_layers):
    """Every ``scan`` of length ``n_layers`` in a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == n_layers:
            found.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _layer_scans(inner, n_layers)
    return found


@pytest.mark.parametrize("entry", ["forward", "encode"])
def test_layer_scan_does_not_slice_kernel_int4_leaves(entry):
    """The copies cannot come back unnoticed: in the jaxpr of a
    kernel-eligible int4 model no ``xs`` operand of the layer scan is an int8
    ``[L, din/2, dout]`` leaf or its ``[L, G, dout]`` scales; they are the
    scan's loop-invariant operands, whole. The one exception is by name
    (``SLICED_KERNEL_INT4``: the leaf kept on the 2-D call)."""
    model, params = _eligible_model()
    L = model.cfg.num_layers
    if entry == "forward":
        args = _forward_args(model)
        jaxpr = jax.make_jaxpr(
            lambda p, *a: model.forward(p, *a, attn_impl="gather")
        )(params, *args)
    else:
        toks = jnp.ones((1, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(model.encode)(
            params, toks, jnp.full((1,), 8, jnp.int32)
        )
    scans = _layer_scans(jaxpr.jaxpr, L)
    assert len(scans) == 1
    eqn = scans[0]
    n_inv = eqn.params["num_consts"] + eqn.params["num_carry"]
    consts = [v.aval for v in eqn.invars[: eqn.params["num_consts"]]]
    xs = [v.aval for v in eqn.invars[n_inv:]]
    layers = params["layers"]
    q4 = [k for k in layers if k.endswith(QUANT4_SUFFIX)]
    assert len(q4) == 7
    assert SLICED_KERNEL_INT4 == ("wk",)
    kept = [layers["wk"], layers["wk" + QUANT4_SUFFIX]]
    # The model's widths are all 1024, so shapes do not tell the leaves
    # apart: count them. Six pairs ride whole, one pair is sliced.
    sigs = lambda avals: sorted((a.shape, str(a.dtype)) for a in avals)
    whole = [
        leaf for k in q4 if k != "wk" + QUANT4_SUFFIX
        for leaf in (layers[k[: -len(QUANT4_SUFFIX)]], layers[k])
    ]
    assert sigs(a for a in consts if sigs([a])[0] in sigs(whole)) == sigs(whole)
    assert sigs(a for a in xs if a.ndim == 3) == sigs(kept)
    assert len([a for a in xs if a.dtype == jnp.int8]) == 1
    # What else the scan slices: the norms, and the layer index.
    assert sorted(a.shape for a in xs if a.ndim < 3) == [
        (L,), (L, 1024), (L, 1024)
    ]


def _subjaxprs(jaxpr):
    """A jaxpr and every jaxpr nested in its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _subjaxprs(inner)


def _pallas_calls(jaxpr):
    """(name, operand avals) of every ``pallas_call`` in a jaxpr."""
    return [
        (eqn.params["name"], [v.aval for v in eqn.invars])
        for sub in _subjaxprs(jaxpr) for eqn in sub.eqns
        if eqn.primitive.name == "pallas_call"
    ]


def test_kernel_call_names_and_operands_as_the_benchmark_reads_them():
    """The device trace names a custom call by the ``pallas_call``'s name,
    and the benchmark's cost functions read the operands in order: the 2-D
    call is ``int4_matmul`` (xe, xo, packed [din/2, dout], scales), the
    stacked one ``int4_matmul_stacked`` with the layer ``s32[1]`` in front
    and the whole stack behind. The model makes six stacked calls a layer
    and one 2-D call (``SLICED_KERNEL_INT4``)."""
    model, params = _eligible_model()
    L = model.cfg.num_layers
    jaxpr = jax.make_jaxpr(
        lambda p, *a: model.forward(p, *a, attn_impl="gather")
    )(params, *_forward_args(model))
    calls = _pallas_calls(jaxpr.jaxpr)
    assert sorted(n for n, _ in calls) == (
        ["int4_matmul"] + ["int4_matmul_stacked"] * 6
    )
    for name, avals in calls:
        shapes = [(a.shape, str(a.dtype)) for a in avals]
        if name == "int4_matmul":
            assert shapes == [
                ((8, 512), "float32"), ((8, 512), "float32"),
                ((512, 1024), "int8"), ((8, 1024), "float32"),
            ]
        else:
            assert shapes == [
                ((1,), "int32"),
                ((8, 512), "float32"), ((8, 512), "float32"),
                ((L, 512, 1024), "int8"), ((L, 8, 1024), "float32"),
            ]


def test_kernel_activations_are_unit_stride_halves_of_x():
    """What XLA runs before a call is decided by how the wrapper makes the
    two activation operands: each is a ``slice`` of the call's ``x`` with
    unit strides, ``[N, din/2]``, the lower half then the upper. No strided
    slice (the even/odd split that cost 1.4 ms of a decode step) and no
    gather can come back unnoticed, in any of the model's seven calls."""
    model, params = _eligible_model()
    jaxpr = jax.make_jaxpr(
        lambda p, *a: model.forward(p, *a, attn_impl="gather")
    )(params, *_forward_args(model))
    seen = []
    for sub in _subjaxprs(jaxpr.jaxpr):
        calls = [e for e in sub.eqns if e.primitive.name == "pallas_call"]
        if not calls:
            continue
        made_by = {v: e for e in sub.eqns for v in e.outvars}
        for call in calls:
            assert call.params["name"].startswith("int4_matmul")
            # Operands: (layer,) xe, xo, packed, scales.
            xe, xo = (made_by[v] for v in call.invars[-4:-2])
            src = xe.invars[0]
            n, din = src.aval.shape
            assert xo.invars[0] is src
            for eqn, start in ((xe, 0), (xo, din // 2)):
                assert eqn.primitive.name == "slice"
                assert eqn.params["strides"] in (None, (1, 1))
                assert eqn.params["start_indices"] == (0, start)
                assert eqn.params["limit_indices"] == (n, start + din // 2)
                assert eqn.outvars[0].aval.shape == (n, din // 2)
            seen.append(call.params["name"])
    assert sorted(seen) == ["int4_matmul"] + ["int4_matmul_stacked"] * 6


def test_dense_stacked_leaf_and_moe_bank_of_same_rank_are_told_apart():
    """A dense stacked leaf [L, din/2, dout] has the rank of one layer's MoE
    bank [E, din/2, dout]: the split goes by name and per-layer shape, so
    the dense leaf rides whole to the kernel and the bank (stacked [L, E,
    din/2, dout]) stays a sliced leaf on the XLA dequant."""
    L, E = 2, 8
    dense_p, dense_s = quantize_leaf_int4(jnp.ones((E, 1024, 256), jnp.float32))
    bank_p, bank_s = quantize_leaf_int4(jnp.ones((L, E, 1024, 256), jnp.float32))
    assert dense_p.shape == bank_p[0].shape  # the trap: same rank, same dims
    layers = {
        "wq": dense_p, "wq" + QUANT4_SUFFIX: dense_s,  # E layers of a dense wq
        "w_gate": bank_p, "w_gate" + QUANT4_SUFFIX: bank_s,
        "attn_norm": jnp.ones((L, 1024)),
    }
    sliced, whole = split_kernel_int4(layers)
    assert set(whole) == {"wq"}
    assert set(sliced) == {"w_gate", "w_gate" + QUANT4_SUFFIX, "attn_norm"}
    lp = _layer_params(
        jax.tree.map(lambda a: a[0], sliced), whole, jnp.int32(0)
    )
    assert isinstance(lp["wq"], StackedInt4) and lp["wq"].packed is dense_p
    assert not isinstance(lp["w_gate"], StackedInt4)
    assert lp["w_gate"].shape == (E, 512, 256)
    assert not use_int4_kernel(lp["w_gate"], lp["w_gate" + QUANT4_SUFFIX])
    # Shapes the kernel does not support stay sliced whatever their name.
    tiny_p, tiny_s = quantize_leaf_int4(jnp.ones((L, 64, 64), jnp.float32))
    sliced, whole = split_kernel_int4(
        {"wq": tiny_p, "wq" + QUANT4_SUFFIX: tiny_s}
    )
    assert not whole and set(sliced) == {"wq", "wq" + QUANT4_SUFFIX}


def test_moe_forward_kernel_for_attention_xla_dequant_for_the_bank():
    """An int4 MoE model at kernel-eligible widths: the attention
    projections go through the kernel (``wk`` on the 2-D call, the other
    three stacked), the expert bank never does, and the logits equal the
    all-XLA fallback's."""
    import production_stack_tpu.ops.int4_matmul as m

    model, params = _eligible_model(
        preset="tiny-mixtral-debug", num_experts=2, num_experts_per_tok=1
    )
    assert params["layers"]["w_gate"].ndim == 4
    args = _forward_args(model)

    def run():
        return np.asarray(
            model.forward(params, *args, attn_impl="gather")[0]
        )

    shapes = []
    orig, orig_2d = m.int4_matmul_stacked, m.int4_matmul

    def recording(x, packed, scales, li, *a, **k):
        shapes.append(packed.shape)
        return orig(x, packed, scales, li, *a, **k)

    def recording_2d(x, packed, scales, *a, **k):
        shapes.append(packed.shape)
        return orig_2d(x, packed, scales, *a, **k)

    m.int4_matmul_stacked, m.int4_matmul = recording, recording_2d
    real_gate = m.use_int4_kernel
    try:
        with_kernel = run()
        assert sorted(shapes) == sorted(
            [params["layers"][k].shape for k in ("wq", "wv", "wo")]
            + [params["layers"]["wk"].shape[1:]]
        )
        m.use_int4_kernel = lambda *a: False
        without = run()
        assert len(shapes) == 4
    finally:
        m.use_int4_kernel = real_gate
        m.int4_matmul_stacked, m.int4_matmul = orig, orig_2d
    scale = np.abs(without).max()
    np.testing.assert_allclose(with_kernel, without, atol=3e-3 * scale)


def test_model_forward_routes_through_kernel():
    """A kernel-eligible model produces the same logits whether the int4
    matmuls run through the Pallas kernel or the XLA dequant fallback."""
    import production_stack_tpu.ops.int4_matmul as m

    model, params = _eligible_model()
    assert use_int4_kernel(
        params["layers"]["wq"][0], params["layers"]["wq_q4s"][0]
    )

    args = _forward_args(model)

    def run():
        return np.asarray(model.forward(params, *args, attn_impl="gather")[0])

    calls = {"n": 0, "n_2d": 0}
    orig, orig_2d = m.int4_matmul_stacked, m.int4_matmul

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    def counting_2d(*a, **k):
        calls["n_2d"] += 1
        return orig_2d(*a, **k)

    m.int4_matmul_stacked, m.int4_matmul = counting, counting_2d
    try:
        with_kernel = run()
        # The layer body is traced once: 7 leaves, wk on the 2-D call.
        assert calls == {"n": 6, "n_2d": 1}
        # Force the fallback by disabling the gate.
        real_gate = m.use_int4_kernel
        m.use_int4_kernel = lambda *a: False
        try:
            without = run()
        finally:
            m.use_int4_kernel = real_gate
        assert calls == {"n": 6, "n_2d": 1}
    finally:
        m.int4_matmul_stacked, m.int4_matmul = orig, orig_2d
    scale = np.abs(without).max()
    np.testing.assert_allclose(with_kernel, without, atol=3e-3 * scale)
