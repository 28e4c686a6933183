"""Weights are data: the reference holds the same arrays as the engine.

The engine makes a preset's weights from its ``--seed`` by a recipe of its
own; this module follows that recipe with the program's *initialisers* (so
the numbers are identical) and nothing of its forward pass:

- unquantised: ``Llama.init_params(PRNGKey(seed))`` under one ``jit``
  (``engine/runner.py::_init_params_sharded``);
- int4 / int8: leaf by leaf, key ``fold_in(PRNGKey(seed), xxh32(name))``,
  ``init_leaf`` then ``quantize_leaf_int4`` (layer matmuls, int4 mode) or
  ``quantize_leaf`` (``_init_params_streamed``).

Dequantisation is this module's own copy of the documented layouts:
int4 is nibble-packed along the contraction axis (even rows in the low
nibble, odd in the high) with one float32 scale per group of contraction
rows and output column (``<name>_q4s``); int8 carries one scale per output
column (``<name>_qs``), and for ``embed`` / ``lm_head`` one per row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def engine_params(model_cfg, seed: int, quantization):
    """The parameter tree the engine serves for ``--seed seed``."""
    from production_stack_tpu.models import llama as prog

    model = prog.Llama(model_cfg)
    rng = jax.random.PRNGKey(seed)
    if not quantization:
        return jax.jit(model.init_params)(rng)
    import xxhash

    shapes = jax.eval_shape(model.init_params, rng)

    def build(name, sds, into):
        key = jax.random.fold_in(
            rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF
        )
        int4 = quantization == "int4" and name in prog.QUANT_LAYER_KEYS
        axis = (-2 if name in prog.QUANT_LAYER_KEYS
                else -1 if name in prog.QUANT_TOP_KEYS else None)
        if axis is None:
            into[name] = jax.jit(
                lambda k: prog.init_leaf(name, sds.shape, sds.dtype, k))(key)
            return

        def init_q(k):
            w = prog.init_leaf(name, sds.shape, sds.dtype, k)
            return (prog.quantize_leaf_int4(w) if int4
                    else prog.quantize_leaf(w, axis=axis))

        q, s = jax.jit(init_q)(key)
        into[name] = q
        into[name + ("_q4s" if int4 else "_qs")] = s

    out = {"layers": {}}
    for name, sds in shapes.items():
        if name != "layers":
            build(name, sds, out)
    for name, sds in shapes["layers"].items():
        build(name, sds, out["layers"])
    return out


def _dequant_int4(packed, scales):
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4).astype(jnp.float32)
    hi = jnp.right_shift(packed, 4).astype(jnp.float32)
    half, out = packed.shape[-2], packed.shape[-1]
    w = jnp.stack([lo, hi], axis=-2).reshape(
        packed.shape[:-2] + (2 * half, out))
    groups = scales.shape[-2]
    w = w.reshape(packed.shape[:-2] + (groups, 2 * half // groups, out))
    w = w * scales[..., :, None, :]
    return w.reshape(packed.shape[:-2] + (2 * half, out))


@jax.jit
def _matmul_leaf(w, q4s, qs):
    if q4s is not None:
        return _dequant_int4(w, q4s)
    if qs is not None:
        return w.astype(jnp.float32) * qs[..., None, :]
    return w.astype(jnp.float32)


def layer_weights(params, li: int):
    """Layer ``li``'s weights for :func:`perf.reference.model.layer`, in
    float32. A MoE layer's unquantised expert banks are the exception: a
    Mixtral layer's experts are 5.6 GB in float32 and even one layer's
    slice of the stored bank is a copy the chip has no room for beside the
    tree, so the whole stacked bank is handed on untouched with ``li``
    beside it, and the layer widens one expert at a time."""
    layers = params["layers"]
    moe = "w_router" in layers
    out = {}
    for name, leaf in layers.items():
        if name.endswith(("_qs", "_q4s")) or name.startswith("lora_"):
            continue
        if name in LAYER_MATMULS:
            q4s = layers.get(name + "_q4s")
            qs = layers.get(name + "_qs")
            if moe and name in ("w_gate", "w_up", "w_down") and q4s is None and qs is None:
                out[name] = leaf
                out["li"] = jnp.int32(li)
            else:
                out[name] = _matmul_leaf(
                    leaf[li], None if q4s is None else q4s[li],
                    None if qs is None else qs[li])
        else:
            out[name] = leaf[li].astype(jnp.float32)
    return out


@jax.jit
def _rows(w, qs, ids):
    rows = w[ids].astype(jnp.float32)
    return rows if qs is None else rows * qs[ids][:, None]


def embed_rows(params, ids):
    """Embedding rows for ``ids`` in float32 (int8 rows carry one scale each)."""
    return _rows(params["embed"], params.get("embed_qs"), ids)


@jax.jit
def _widen_rows(w, qs):
    w = w.astype(jnp.float32)
    return w if qs is None else w * qs[:, None]


def head_weights(params):
    """(final_norm [D], lm_head [V, D]) in float32."""
    name = "lm_head" if "lm_head" in params else "embed"
    return (params["final_norm"].astype(jnp.float32),
            _widen_rows(params[name], params.get(name + "_qs")))
