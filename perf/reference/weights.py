"""Weights are data: the reference holds the same arrays as the engine.
What every reference module shares: the recipe, the dequantisation of the
documented layouts, the embedding rows and the head.

The engine makes a preset's weights from its ``--seed`` by a recipe of its
own; :func:`engine_params` follows that recipe with the *initialisers* of
the program's model object, which the reference module hands in (so the
numbers are identical), and nothing of its forward pass:

- unquantised: ``model.init_params(PRNGKey(seed))`` under one ``jit``
  (``engine/runner.py::_init_params_sharded``);
- int4 / int8: leaf by leaf, key ``fold_in(PRNGKey(seed), xxh32(name))``,
  ``init_leaf`` then ``quantize_leaf_int4`` (layer matmuls, int4 mode) or
  ``quantize_leaf`` (``_init_params_streamed``).

Which leaves are quantised, and by what, is the model object's to say: an
object that carries ``RECIPE``'s names (same names and signatures as
``models/llama.py``'s) is followed in all five, one that carries none gets
``models/llama.py``'s, and one that carries some but not all is refused. A
model class with other quantised leaves brings them on the object and
touches no file here.

Dequantisation is this module's own copy of the documented layouts:
int4 is nibble-packed along the contraction axis (even rows in the low
nibble, odd in the high) with one float32 scale per group of contraction
rows and output column (``<name>_q4s``); int8 carries one scale per output
column (``<name>_qs``), and for ``embed`` / ``lm_head`` one per row.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

RECIPE = ("QUANT_LAYER_KEYS", "QUANT_TOP_KEYS", "init_leaf", "quantize_leaf",
          "quantize_leaf_int4")


def recipe_of(model):
    """``RECIPE``'s names, all from ``model`` if it carries any of them (a
    partial set is an error: the rest would silently be another model's),
    else all from ``models/llama.py``."""
    own = [n for n in RECIPE if hasattr(model, n)]
    if own and len(own) < len(RECIPE):
        raise ValueError(
            f"{type(model).__name__} carries {own} of the weights recipe but "
            f"not {[n for n in RECIPE if n not in own]}: all or none")
    if not own:
        from production_stack_tpu.models import llama as model
    return types.SimpleNamespace(**{n: getattr(model, n) for n in RECIPE})


def engine_params(model, seed: int, quantization):
    """The parameter tree the engine serves for ``--seed seed``; ``model``
    is the program's model object for the configuration."""
    rng = jax.random.PRNGKey(seed)
    if not quantization:
        return jax.jit(model.init_params)(rng)
    import xxhash

    recipe = recipe_of(model)
    shapes = jax.eval_shape(model.init_params, rng)

    def build(name, sds, into):
        key = jax.random.fold_in(
            rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF
        )
        int4 = quantization == "int4" and name in recipe.QUANT_LAYER_KEYS
        axis = (-2 if name in recipe.QUANT_LAYER_KEYS
                else -1 if name in recipe.QUANT_TOP_KEYS else None)
        if axis is None:
            into[name] = jax.jit(
                lambda k: recipe.init_leaf(name, sds.shape, sds.dtype, k))(key)
            return

        def init_q(k):
            w = recipe.init_leaf(name, sds.shape, sds.dtype, k)
            return (recipe.quantize_leaf_int4(w) if int4
                    else recipe.quantize_leaf(w, axis=axis))

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
def matmul_leaf(w, q4s, qs):
    """One stored matmul leaf in float32: int4 with its group scales
    ``q4s``, int8 with its column scales ``qs``, or neither."""
    if q4s is not None:
        return _dequant_int4(w, q4s)
    if qs is not None:
        return w.astype(jnp.float32) * qs[..., None, :]
    return w.astype(jnp.float32)


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
