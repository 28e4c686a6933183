"""The contract between the engine and a model class, stated once.

A model arrives as two objects: a frozen config (a dataclass that inherits
:class:`ModelConfig`) and a class of stateless functions bound to it (one
that inherits :class:`Model`). The engine reads off them exactly what is
defined here, as plain attributes: a property this module does not define
is not one the engine may ask for, and a misspelt one is an
``AttributeError``, not a silent ``False``. ``models/registry.py::MODEL_TYPES``
binds an HF ``model_type`` to a config reader and a class.

What a class brings itself: ``init_params`` (and its ``init_leaf``: the key
derivations and initialisers are each class's own, and the benchmark's
weights are made from them by seed), ``make_kv_cache`` and ``forward``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


class ModelConfig:
    """What the engine asks of any model config, with the answer of a model
    whose per-request memory is a plain list of K+V pages in every layer.

    A config class is a frozen dataclass that inherits this and carries, as
    fields or properties of its own: ``vocab_size``, ``num_layers``,
    ``num_heads``, ``num_kv_heads``, ``head_dim``, ``dtype``, ``name``,
    ``eos_token_ids``, ``max_position_embeddings``.
    """

    # The kinds of per-request memory (``engine/config.py::_refusals`` has
    # what each cannot be served with).
    recurrent = False  # per-sequence state slots beside the pages
    latent_pages = False  # a page is one latent row a token, not K and V
    window_pages = False  # a page group released below ``sliding_window``
    wide_head_pages = False  # heads wider than the paged kernels' one-byte path
    # More layers of pages than of weights: a step runs the layer stack
    # ``num_kv_layers // num_layers`` times, each pass on slots of its own.
    looped = False
    # Layers of matrix-valued state whose slots a decode step reads and
    # writes whole: what a step's trace record says its kernel's bytes follow.
    num_state_layers = 0
    num_experts = 0  # experts an expert-parallel mesh could divide
    sliding_window = 0  # tokens a window layer reads (0: no window layers)
    # Draft layers of the model's own (multi-token prediction) that a decode
    # step may run and verify on the device (``--speculative-mtp``); their
    # pages are further layers of the global group, stored one slot ahead.
    mtp_layers = 0

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages: the KV pool is sized from these."""
        return self.num_layers

    @property
    def paged_query_shape(self) -> "tuple[int, int]":
        """(heads, lanes a head) of the queries a layer hands the paged
        attention kernels: what their choice of path goes by."""
        return self.num_heads, self.head_dim

    @property
    def global_window(self) -> int:
        """The widest view a layer over the global page group has: 0 where
        some layer there reads a row's whole context (window layers with a
        page group of their own are not among them)."""
        return 0 if self.window_pages else self.sliding_window

    def page_bytes(self, block_size: int, itemsize: int,
                   tp: int = 1, pp: int = 1) -> int:
        """Bytes one device holds of one page of the global group: K and V
        of ``block_size`` tokens over the layers that hold pages, the heads
        divided by ``tp`` and the layers by ``pp``. A class whose page has
        another shape says its own (it is served on one device)."""
        return (2 * max(self.num_kv_layers // pp, 1) * block_size
                * max(self.num_kv_heads // tp, 1) * self.head_dim * itemsize)

    def window_page_bytes(self, block_size: int, itemsize: int) -> int:
        """Bytes of one page of the window group, over every window layer."""
        return 0

    def state_bytes_per_slot(self) -> int:
        """Bytes of one sequence's recurrent state over every state layer."""
        return 0


class Model:
    """Stateless model functions bound to a config: the runner's model
    object. The defaults are those of a class served on one device whose
    cache is a dict of arrays with an ``aux`` entry."""

    # Names of the numbers a step reports beside its tokens (``step_aux``);
    # the runner appends one row each to a step's packed tokens.
    AUX_NAMES: tuple = ()
    # A prefill step runs the cross-decoder on sampled positions alone: the
    # runner says which rows a token is sampled from (``sample_rows``).
    SKIPS_CROSS_DECODER = False
    # ``forward`` takes ``token_budget``, a step's bound on real tokens (a
    # class whose config is ``recurrent`` is told it anyway).
    TOKEN_BUDGET = False

    def __init__(self, cfg):
        self.cfg = cfg

    def param_pspecs(self, pipeline: bool = False, quantize=False) -> Params:
        """Every leaf of ``init_params``'s tree replicated (the engine
        refuses a mesh and quantisation at start-up for a class that keeps
        this default)."""
        return jax.tree.map(
            lambda _: P(), jax.eval_shape(self.init_params, jax.random.PRNGKey(0)))

    def cache_pspec(self, pipeline: bool = False) -> Dict[str, P]:
        """Every array of ``make_kv_cache``'s dict replicated."""
        return {k: P() for k in jax.eval_shape(lambda: self.make_kv_cache(1, 1))}

    def mtp_forward(self, *args, **kwargs):
        """The draft module over positions ``forward`` has just run (with
        ``return_hidden``): a class whose config has ``mtp_layers`` brings it
        (``models/exaone_moe.py``); the engine asks no other class
        (``engine/config.py::refuse_mtp``)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no multi-token-prediction module")

    @staticmethod
    def step_aux(cache) -> jax.Array:
        """``[len(AUX_NAMES)]`` float32 the step left in its cache."""
        return cache["aux"]


# ----------------------------------------------------------------------------
# Layers and initialisers more than one class uses (``models/llama.py``
# re-exports them under the same names).
# ----------------------------------------------------------------------------

QUANT_SUFFIX = "_qs"  # an int8 leaf's scale is its sibling leaf ``<name>_qs``
QUANT_TOP_KEYS = ("embed", "lm_head")


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One param leaf's random init, matching :meth:`Llama.init_params`
    distributions by name. Used by the runner's streamed materialization
    (leaf-by-leaf, jitted straight into its device sharding) so big-model
    init never holds the full bf16 tree anywhere."""
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if name.startswith(("b", "lora_")) or not shape:  # a scalar is a bias
        return jnp.zeros(shape, dtype)
    fan_in = (shape[-1] if name in QUANT_TOP_KEYS or len(shape) == 1
              else shape[-2])
    return (
        jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    ).astype(dtype)


def _rms_norm(
    x: jax.Array, w: jax.Array, eps: float, unit_offset: bool = False
) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    if unit_offset:  # Gemma stores w with effective weight (1 + w), fp32 math
        return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return normed.astype(x.dtype) * w


def _embed_lookup(params: Params, tokens: jax.Array, cfg: "ModelConfig") -> jax.Array:
    """Token embedding gather; int8 tables dequantize with their per-row
    scale (the same rows the tied unembed scales by)."""
    x = params["embed"][tokens]
    s = params.get("embed" + QUANT_SUFFIX)
    if s is not None:
        x = (x.astype(jnp.float32) * s[tokens][..., None]).astype(cfg.jdtype)
    return x


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies ``[head_dim / 2]`` (float64, on the host):
    each a blend of the default ``f = theta^(-2i / head_dim)`` and the
    interpolated ``f / factor`` by a linear ramp between the (whole)
    dimensions that turn ``beta_fast`` and ``beta_slow`` times in
    ``original_max_position`` positions: fast lanes keep ``f``, slow lanes
    take ``f / factor``. The caller scales ``cos`` and ``sin`` by the
    config's ``attention_factor``."""
    half = head_dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)

    def turning(rotations: float) -> float:  # the dimension that turns so often
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turning(beta_fast)), 0)
    high = min(math.ceil(turning(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """HF-Llama rotate-half convention; x: [B, T, H, hd]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)
