"""Llama-architecture decoder in functional JAX with a paged KV cache.

This is the serving engine's compute core — the piece the reference stack
outsources to the vLLM container image (`helm/templates/
deployment-vllm-multi.yaml:101-118`). One architecture class covers the
Llama-3 / Llama-2 / Mistral / Qwen2 family: RMSNorm, rotary embeddings,
grouped-query attention, SwiGLU MLP, optional QKV biases (Qwen2), optional
tied embeddings.

Design notes (TPU-first):
- Params are a plain pytree with layers **stacked on a leading axis** and the
  forward pass is a single ``lax.scan`` over layers — one compiled layer body
  regardless of depth, fast XLA compiles even for 80-layer models.
- One unified forward for prefill and decode: tokens are ``[B, T]`` (decode is
  ``T=1``, prefill ``B=1`` chunks). KV is written into cache pages first, then
  attention reads through the block table, which makes prefix-cache hits and
  chunked prefill the same code path.
- Sharding is declarative: :func:`param_pspecs` / :func:`cache_pspec` return
  `PartitionSpec` trees (tp over heads/ffn, optional pp over the stacked layer
  axis); `jit` + `NamedSharding` lets XLA insert the ICI collectives. No
  NCCL analogue to manage.
- Matmuls accumulate in fp32 (``preferred_element_type``) with bf16 weights:
  MXU-native.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..logging_utils import init_logger
from ..ops.attention import paged_attention, window_eff
from ..parallel.mesh import AXIS_EXPERT, AXIS_PIPELINE, AXIS_TENSOR

logger = init_logger(__name__)

Params = Dict[str, Any]

# ----------------------------------------------------------------------------
# Weight-only int8 quantization (per-output-channel symmetric).
#
# The reference serves its 8B benchmark model on a 40 GiB A100
# (`tutorials/07-benchmark-multi-round-qa-single-gpu.md:5`); one v5e chip has
# 16 GiB, so bf16 8B weights (~16 GiB) cannot sit next to their KV. Weight-only
# int8 halves weight HBM (and decode's weight-read bandwidth, the decode-step
# floor) while keeping activations/accumulation in bf16/fp32 on the MXU:
# ``y = (x @ w_int8→bf16) * scale`` is exact for per-output-channel scales, and
# XLA fuses the int8→bf16 convert into the matmul's HBM read.
#
# The scale for quantized leaf ``w`` is stored as sibling leaf ``w_qs``.
# Matmul weights ([..., in, out] layout) quantize over their input dim
# (axis -2); embedding tables ([V, D]) over the hidden dim (axis -1) so one
# per-row scale serves both the lookup and the tied unembed.
# ----------------------------------------------------------------------------

QUANT_SUFFIX = "_qs"
QUANT4_SUFFIX = "_q4s"
QUANT4_GROUP = 128
QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
QUANT_TOP_KEYS = ("embed", "lm_head")


def quantize_leaf(w: jax.Array, axis: int = -2) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel int8: returns (int8 weights, fp32 scales).
    ``axis`` is the contraction (input) dim the scale reduces over."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / jnp.expand_dims(s, axis)), -127, 127)
    return q.astype(jnp.int8), s


def _q4_group(din: int) -> int:
    """Largest group size ≤ QUANT4_GROUP dividing the contraction dim (tiny
    debug models have dims < 128; real models hit 128 exactly)."""
    g = QUANT4_GROUP
    while din % g:
        g //= 2
        if g < 2:
            raise ValueError(f"int4 needs an even contraction dim, got {din}")
    return g


def quantize_leaf_int4(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric group-wise int4 over the contraction axis (-2), the
    AWQ/GPTQ-family layout (group size 128). Returns (packed int8
    [..., in/2, out] — even contraction rows in the low nibble, odd in the
    high — and fp32 scales [..., in/G, out]). Packed int8, not jnp.int4:
    the Pallas kernel reads the packed bytes and splits the nibble planes
    itself (ops/int4_matmul.py)."""
    wf = w.astype(jnp.float32)
    *lead, din, dout = wf.shape
    g = _q4_group(din)
    wg = wf.reshape(*lead, din // g, g, dout)
    amax = jnp.max(jnp.abs(wg), axis=-2)  # [..., G, out]
    s = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wg / s[..., :, None, :]), -7, 7).astype(jnp.int8)
    q = q.reshape(*lead, din, dout)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, jnp.int8(0x0F)), jnp.left_shift(hi, 4)
    )
    return packed, s


def dequant_int4(packed: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    """Unpack + scale an int4 weight to the compute dtype. All ops here are
    elementwise/reshape on the packed array — XLA fuses them into the
    consuming dot's HBM read, so the stream stays 0.5 byte/weight."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)  # sign-extended
    hi = jnp.right_shift(packed, 4)  # arithmetic shift
    w = jnp.stack([lo, hi], axis=-2)  # [..., in/2, 2, out]
    shape = w.shape[:-3] + (w.shape[-3] * 2, w.shape[-1])
    w = w.reshape(shape).astype(dtype)
    G = scales.shape[-2]
    g = shape[-2] // G
    w = w.reshape(shape[:-2] + (G, g, shape[-1])) * scales[
        ..., :, None, :
    ].astype(dtype)
    return w.reshape(shape)


def quantize_tree(params: Params, mode: str = "int8") -> Params:
    """Quantize all matmul weights of a loaded param tree in place.
    Used by the HF-checkpoint path (host-side); random-init presets use the
    streamed per-leaf path in the runner instead (never holds the bf16 tree).
    ``mode``: "int8" (per-channel) or "int4" (group-wise for the per-layer
    matmuls; embed/lm_head stay int8 — the gather and post-matmul-scale
    paths are exact there and the per-step byte win is negligible)."""
    layers = params["layers"]
    for k in QUANT_LAYER_KEYS:
        if k in layers:
            if mode == "int4":
                q, s = quantize_leaf_int4(layers[k])
                layers[k] = q
                layers[k + QUANT4_SUFFIX] = s
            else:
                q, s = quantize_leaf(layers[k], axis=-2)
                layers[k] = q
                layers[k + QUANT_SUFFIX] = s
    for k in QUANT_TOP_KEYS:
        if k in params:
            q, s = quantize_leaf(params[k], axis=-1)
            params[k] = q
            params[k + QUANT_SUFFIX] = s
    return params


def _wcast(w: jax.Array, dtype) -> jax.Array:
    """Weight operand for a matmul: int8 leaves convert on the fly (XLA
    fuses the convert into the dot's HBM read — the bandwidth saving is
    kept); everything else passes through."""
    return w.astype(dtype) if w.dtype == jnp.int8 else w


def _wmat(p: Params, name: str, dtype) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Matmul weight operand under any quantization mode.

    Returns (operand in compute dtype, post-matmul scale or None): int4
    leaves dequantize pre-matmul (group scales vary along the contraction
    dim, so no post-scale exists); int8 leaves convert on the fly — a bare
    convert XLA fuses into the dot's HBM read — and hand back their
    per-output-channel scale for the caller to apply post-matmul (exact).

    NOTE: the XLA int4 dequant does NOT fuse (the unpack's stack/reshape
    defeats operand fusion, materializing the bf16 weights per layer) —
    serving-shape int4 matmuls go through :func:`_qdot`'s Pallas kernel
    instead; this path remains for tiny/odd shapes and the MoE bank."""
    w = p[name]
    q4s = p.get(name + QUANT4_SUFFIX)
    if q4s is not None:
        return dequant_int4(w, q4s, dtype), None
    return _wcast(w, dtype), p.get(name + QUANT_SUFFIX)


def _qdot(
    x: jax.Array, p: Params, name: str
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``x [..., din] @ weight`` under any quantization mode. Returns
    (fp32 output, post-matmul scale or None). int4 weights at serving
    shapes stream through the Pallas kernel (0.5 byte/weight from HBM);
    everything else is a plain einsum over :func:`_wmat`'s operand."""
    q4s = p.get(name + QUANT4_SUFFIX)
    if q4s is not None:
        from ..ops.int4_matmul import use_int4_kernel, int4_matmul

        if use_int4_kernel(p[name], q4s):
            lead = x.shape[:-1]
            y = int4_matmul(x.reshape(-1, x.shape[-1]), p[name], q4s)
            return y.reshape(*lead, y.shape[-1]), None
    w, s = _wmat(p, name, x.dtype)
    out = jnp.einsum("...d,do->...o", x, w, preferred_element_type=jnp.float32)
    return out, s


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One param leaf's random init, matching :meth:`Llama.init_params`
    distributions by name. Used by the runner's streamed materialization
    (leaf-by-leaf, jitted straight into its device sharding) so big-model
    init never holds the full bf16 tree anywhere."""
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if name.startswith(("b", "lora_")):
        return jnp.zeros(shape, dtype)
    fan_in = shape[-1] if name in QUANT_TOP_KEYS else shape[-2]
    return (
        jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    ).astype(dtype)


def pp_compose(run_stage, x, replicated, scanned, pp_size: int, mesh):
    """Compose layer-stages across the ``pp`` mesh axis by rotating
    activations (TPU-native pipeline parallel; replaces the reference's
    Ray-cluster PP, ``helm/templates/ray-cluster.yaml:560-566``).

    Each pp rank holds ``L/pp`` layers (the ``scanned`` pytrees are sharded on
    their leading layer axis). The activation makes ``pp`` hops: at hop ``i``
    rank ``i`` holds the correctly-composed prefix, applies its local layers,
    and ``ppermute``s the result to rank ``i+1``; other ranks compute on
    rotated (discarded) lanes, so wall-clock equals the sequential depth while
    HBM per device drops by ``pp``. Rank 0 ends with the full composition,
    which a masked ``psum`` broadcasts. Collectives are point-to-point
    ``ppermute``s — DCN-friendly, exactly the inter-host traffic pattern PP
    wants (the tp all-reduces stay inside each stage on ICI, handled by GSPMD
    auto mode since only ``pp`` is manual here).

    ``run_stage(x, scanned_local, gate)`` applies the local layer stack;
    ``gate`` is a bool scalar — True only on the hop where this rank's input
    is the real composition, letting the stage suppress side effects (KV
    cache writes) on garbage lanes. Returns ``(x, scanned_local_out)``.

    ``replicated`` arrays (rope tables, block tables, …) are passed through
    explicitly — closed-over traced values would carry auto-mesh shardings
    that clash with the manual-``pp`` context.
    """
    perm = [(j, (j + 1) % pp_size) for j in range(pp_size)]

    def body(x, repl, *scanned_local):
        rank = jax.lax.axis_index(AXIS_PIPELINE)
        out_scanned = scanned_local
        for i in range(pp_size):
            x_out, out_scanned = run_stage(x, repl, out_scanned, rank == i)
            x = jax.lax.ppermute(x_out, AXIS_PIPELINE, perm)
        x = jax.lax.psum(
            jnp.where(rank == 0, x, jnp.zeros_like(x)), AXIS_PIPELINE
        )
        return (x, *out_scanned)

    pp_spec = P(AXIS_PIPELINE)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), *([pp_spec] * len(scanned))),
        out_specs=(P(), *([pp_spec] * len(scanned))),
        axis_names={AXIS_PIPELINE},
        check_vma=False,
    )(x, replicated, *scanned)
    return out[0], out[1:]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    rope_theta: float = 10000.0
    # Llama-3.1-style rope scaling (HF config.json "rope_scaling" with
    # rope_type "llama3"). factor 0 = disabled. Without this, checkpoints
    # trained with scaled rope are silently wrong past their original
    # context (e.g. Llama-3.1 beyond 8k).
    rope_scaling_factor: float = 0.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style QKV biases
    # Mixture-of-experts (Mixtral-style sparse SwiGLU MLP; HF
    # ``num_local_experts`` / ``num_experts_per_tok``). 0 experts = dense.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Qwen3-style per-head RMSNorm on q/k (applied over head_dim, before
    # rope; params q_norm/k_norm [L, hd]).
    qk_norm: bool = False
    # Gemma-family architecture knobs (all default to the Llama conventions).
    hidden_act: str = "silu"  # silu | gelu_tanh (Gemma GeGLU)
    norm_unit_offset: bool = False  # RMSNorm weight is (1 + w) (Gemma)
    embed_scale: bool = False  # scale embeddings by sqrt(D) (Gemma)
    query_pre_attn_scalar: float = 0.0  # attn scale override (Gemma-2; 0=hd)
    attn_logit_softcap: float = 0.0  # tanh cap on attention logits (Gemma-2)
    final_logit_softcap: float = 0.0  # tanh cap on LM-head logits (Gemma-2)
    post_block_norms: bool = False  # Gemma-2 post-attn / post-mlp RMSNorms
    # Sliding-window (local) attention: each query sees at most the last
    # `sliding_window` positions (Mistral-v0.1, Gemma-2). With
    # `sliding_window_pattern` = N > 1, every Nth layer (li+1 ≡ 0 mod N) is
    # global and the rest are local (Gemma-2: N=2); 1 = all layers local.
    sliding_window: int = 0
    sliding_window_pattern: int = 1
    dtype: str = "bfloat16"
    # Serving identity / tokenizer hints (not part of the math).
    name: str = "llama"
    eos_token_ids: Tuple[int, ...] = (2,)
    bos_token_id: Optional[int] = 1

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def attn_scale(self) -> float:
        base = self.query_pre_attn_scalar or self.head_dim
        return 1.0 / math.sqrt(base)

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim


class Llama:
    """Stateless model functions bound to a config."""

    def __init__(self, cfg: LlamaConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Params:
        """Random (serving-scale-correct) initialization, for tests/bench."""
        cfg = self.cfg
        d = cfg.jdtype
        k = jax.random.split(rng, 9)
        D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

        def dense(key, shape, fan_in):
            return (
                jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(d)

        if cfg.num_experts:
            E = cfg.num_experts
            mlp = {
                # Router kept [D, E] so routing is a plain x @ w (HF stores
                # the transpose). Experts are stacked on their own axis so
                # the whole bank feeds one grouped matmul (ragged_dot) or one
                # expert-batched einsum — and shards over the ep mesh axis.
                "w_router": dense(k[8], (L, D, E), D),
                "w_gate": dense(k[5], (L, E, D, F), D),
                "w_up": dense(k[6], (L, E, D, F), D),
                "w_down": dense(k[7], (L, E, F, D), F),
            }
        else:
            mlp = {
                "w_gate": dense(k[5], (L, D, F), D),
                "w_up": dense(k[6], (L, D, F), D),
                "w_down": dense(k[7], (L, F, D), F),
            }
        params: Params = {
            "embed": dense(k[0], (cfg.vocab_size, D), D),
            "layers": {
                "attn_norm": jnp.ones((L, D), d),
                "wq": dense(k[1], (L, D, cfg.q_size), D),
                "wk": dense(k[2], (L, D, cfg.kv_size), D),
                "wv": dense(k[3], (L, D, cfg.kv_size), D),
                "wo": dense(k[4], (L, cfg.q_size, D), cfg.q_size),
                "mlp_norm": jnp.ones((L, D), d),
                **mlp,
            },
            "final_norm": jnp.ones((D,), d),
        }
        if cfg.attention_bias:
            params["layers"]["bq"] = jnp.zeros((L, cfg.q_size), d)
            params["layers"]["bk"] = jnp.zeros((L, cfg.kv_size), d)
            params["layers"]["bv"] = jnp.zeros((L, cfg.kv_size), d)
        if cfg.qk_norm:
            params["layers"]["q_norm"] = jnp.ones((L, cfg.head_dim), d)
            params["layers"]["k_norm"] = jnp.ones((L, cfg.head_dim), d)
        if cfg.post_block_norms:
            params["layers"]["post_attn_norm"] = jnp.ones((L, D), d)
            params["layers"]["post_mlp_norm"] = jnp.ones((L, D), d)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = dense(k[0], (cfg.vocab_size, D), D)
        return params

    def param_pspecs(
        self, pipeline: bool = False, quantize=False
    ) -> Params:
        """PartitionSpec tree matching :meth:`init_params`.

        tp shards attention heads and the FFN hidden dim (Megatron layout:
        column-parallel in-projections, row-parallel out-projections — XLA
        emits the single all-reduce per block that layout implies). With
        ``pipeline=True`` the stacked layer axis is additionally sharded over
        pp, giving layer-stage parallelism without restructuring the tree.
        ``quantize``: False, or a mode — "int8"/True adds the per-channel
        scale leaves (``*_qs``) sharded like their weight's output channels;
        "int4" adds group-wise scale leaves (``*_q4s``, same rank and mesh
        axes as their weight — only the contraction dim shrinks) for the
        per-layer matmuls plus int8 ``*_qs`` for embed/lm_head.
        """
        mode = "int8" if quantize is True else quantize
        pp = "pp" if pipeline else None
        if self.cfg.num_experts:
            # Expert bank: experts over ep, FFN hidden over tp (each expert
            # is itself Megatron-sharded). The combine einsum's reduction
            # over E becomes the one all-reduce over ep XLA inserts.
            mlp_specs = {
                "w_router": P(pp, None, None),
                "w_gate": P(pp, AXIS_EXPERT, None, AXIS_TENSOR),
                "w_up": P(pp, AXIS_EXPERT, None, AXIS_TENSOR),
                "w_down": P(pp, AXIS_EXPERT, AXIS_TENSOR, None),
            }
        else:
            mlp_specs = {
                "w_gate": P(pp, None, AXIS_TENSOR),
                "w_up": P(pp, None, AXIS_TENSOR),
                "w_down": P(pp, AXIS_TENSOR, None),
            }
        specs: Params = {
            "embed": P(None, AXIS_TENSOR),
            "layers": {
                "attn_norm": P(pp, None),
                "wq": P(pp, None, AXIS_TENSOR),
                "wk": P(pp, None, AXIS_TENSOR),
                "wv": P(pp, None, AXIS_TENSOR),
                "wo": P(pp, AXIS_TENSOR, None),
                "mlp_norm": P(pp, None),
                **mlp_specs,
            },
            "final_norm": P(None),
        }
        if self.cfg.attention_bias:
            specs["layers"]["bq"] = P(pp, AXIS_TENSOR)
            specs["layers"]["bk"] = P(pp, AXIS_TENSOR)
            specs["layers"]["bv"] = P(pp, AXIS_TENSOR)
        if self.cfg.qk_norm:
            specs["layers"]["q_norm"] = P(pp, None)
            specs["layers"]["k_norm"] = P(pp, None)
        if self.cfg.post_block_norms:
            specs["layers"]["post_attn_norm"] = P(pp, None)
            specs["layers"]["post_mlp_norm"] = P(pp, None)
        if not self.cfg.tie_word_embeddings:
            specs["lm_head"] = P(None, AXIS_TENSOR)
        if mode:
            # int8 scale spec = weight spec minus the reduced (input) axis:
            # the scale shards exactly like its weight's output channels.
            # int4 scale spec = weight spec verbatim (the group axis lives
            # where the contraction axis does and shards the same way).
            def drop_axis(spec: P, ndim: int, axis: int) -> P:
                ent = list(spec) + [None] * (ndim - len(spec))
                del ent[axis]
                return P(*ent)

            moe = bool(self.cfg.num_experts)
            for k in QUANT_LAYER_KEYS:
                if k in specs["layers"]:
                    if mode == "int4":
                        specs["layers"][k + QUANT4_SUFFIX] = specs["layers"][k]
                        continue
                    ndim = 4 if (moe and k in ("w_gate", "w_up", "w_down")) else 3
                    specs["layers"][k + QUANT_SUFFIX] = drop_axis(
                        specs["layers"][k], ndim, -2
                    )
            for k in QUANT_TOP_KEYS:
                if k in specs:
                    specs[k + QUANT_SUFFIX] = drop_axis(specs[k], 2, -1)
        return specs

    # ------------------------------------------------------------------
    # LoRA bank (stacked adapter slots — engine/lora.py owns the registry)
    # ------------------------------------------------------------------

    LORA_TARGETS = ("wq", "wk", "wv", "wo")

    def init_lora_bank(self, max_loras: int, max_rank: int) -> Params:
        """Zero-filled stacked adapter bank, merged into params["layers"]:
        ``lora_a_<t>`` [L, slots, in, r], ``lora_b_<t>`` [L, slots, r, out].
        Slot 0 stays zero forever = "no adapter" (exact no-op delta)."""
        cfg = self.cfg
        d = cfg.jdtype
        L, S, R = cfg.num_layers, max_loras + 1, max_rank
        dims = {
            "wq": (cfg.hidden_size, cfg.q_size),
            "wk": (cfg.hidden_size, cfg.kv_size),
            "wv": (cfg.hidden_size, cfg.kv_size),
            "wo": (cfg.q_size, cfg.hidden_size),
        }
        bank: Params = {}
        for t, (din, dout) in dims.items():
            bank[f"lora_a_{t}"] = jnp.zeros((L, S, din, R), d)
            bank[f"lora_b_{t}"] = jnp.zeros((L, S, R, dout), d)
        return bank

    def lora_pspecs(self, pipeline: bool = False) -> Params:
        """PartitionSpecs for the bank: B matrices follow their projection's
        output sharding (column-parallel q/k/v), A for wo follows its input
        sharding (row-parallel) — the deltas then compose with the base
        matmuls under the same collectives XLA already inserts."""
        pp = "pp" if pipeline else None
        return {
            "lora_a_wq": P(pp, None, None, None),
            "lora_b_wq": P(pp, None, None, AXIS_TENSOR),
            "lora_a_wk": P(pp, None, None, None),
            "lora_b_wk": P(pp, None, None, AXIS_TENSOR),
            "lora_a_wv": P(pp, None, None, None),
            "lora_b_wv": P(pp, None, None, AXIS_TENSOR),
            "lora_a_wo": P(pp, None, AXIS_TENSOR, None),
            "lora_b_wo": P(pp, None, None, None),
        }

    # ------------------------------------------------------------------
    # KV cache
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None
    ) -> jax.Array:
        # One combined array [L, nb, 2, bs, KH*hd]: a page holds its K rows
        # (index 0 of dim 2) then V rows (index 1), each token row spanning
        # all kv heads in the lane dimension. One DMA moves a whole page in
        # the pallas kernel, the write path is a single scatter, and the
        # minor dims (bs, KH*hd) are sublane/lane tiling-exact — a
        # [..., KH, hd] tail would pad KH=8 up to the 16-sublane tile and
        # physically double the cache.
        cfg = self.cfg
        shape = (
            cfg.num_layers, num_blocks, 2, block_size,
            cfg.num_kv_heads * cfg.head_dim,
        )
        d = jnp.dtype(dtype) if dtype else cfg.jdtype
        return jnp.zeros(shape, d)

    @staticmethod
    def cache_pspec(pipeline: bool = False) -> P:
        # [L, nb, 2, bs, KH*hd] — the head-folded lane dim shards over tp
        # (shard boundaries align with head boundaries when tp | KH); layers
        # over pp when the engine runs pipeline-parallel (each stage holds
        # its layers' pages).
        pp = AXIS_PIPELINE if pipeline else None
        return P(pp, None, None, None, AXIS_TENSOR)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def forward(
        self,
        params: Params,
        tokens: jax.Array,  # [B, T] int32
        positions: jax.Array,  # [B, T] int32 absolute positions (pad: any)
        write_idx: jax.Array,  # [B, T] int32 flat slot idx (nb*bs => dropped)
        block_tables: jax.Array,  # [B, W] int32
        kv_lens: jax.Array,  # [B] int32 valid kv len AFTER this step's writes
        last_idx: jax.Array,  # [B] int32 index in T of each row's last token
        kv_cache: jax.Array,  # [L, nb, 2, bs, KH*hd] (donated by caller's jit)
        *,
        lora_idx: Optional[jax.Array] = None,  # [B] int32 bank slots (0=none)
        lora_scale: Optional[jax.Array] = None,  # [B] f32 alpha/r per row
        attn_impl: str = "auto",
        moe_impl: str = "auto",
        pp_size: int = 1,
        mesh=None,
        all_logits: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        """One engine step. Returns (last-token logits [B, V], new cache) —
        or ([B, T, V] logits for every position when ``all_logits`` (the
        speculative-decoding verify step scores each draft position in one
        pass; ``last_idx`` is ignored).

        With ``pp_size > 1`` the stacked layer axis (params and cache) is
        sharded over the ``pp`` mesh axis and composed via
        :func:`pp_compose`. ``mesh`` is the engine mesh whenever it spans
        more than one device: the Pallas attention kernels then run per
        shard (``ops/attention.py``).
        """
        cfg = self.cfg
        B, T = tokens.shape
        nb, bs = kv_cache.shape[1], kv_cache.shape[3]
        scale = cfg.attn_scale
        offset = cfg.norm_unit_offset

        x = _embed_lookup(params, tokens, cfg)  # [B, T, D]
        if cfg.embed_scale:
            # HF-Gemma convention: the sqrt(D) normalizer is rounded to the
            # model dtype before multiplying.
            x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
        rope_cos, rope_sin = _rope_tables(positions, cfg)
        flat_write_real = write_idx.reshape(-1)  # [B*T]
        has_lora = "lora_a_wq" in params["layers"]
        if has_lora and lora_idx is None:
            lora_idx = jnp.zeros((B,), jnp.int32)
            lora_scale = jnp.zeros((B,), jnp.float32)

        def lora_delta(lp, t: str, inp: jax.Array) -> jax.Array:
            """scaling * (inp @ A[slot]) @ B[slot] per batch row (slot 0 is
            zeros, so no-adapter rows get an exact zero delta)."""
            a = lp[f"lora_a_{t}"][lora_idx]  # [B, in, r]
            b = lp[f"lora_b_{t}"][lora_idx]  # [B, r, out]
            d = jnp.einsum(
                "btd,bdr->btr", inp, a, preferred_element_type=jnp.float32
            )
            d = jnp.einsum(
                "btr,bro->bto", d.astype(b.dtype), b,
                preferred_element_type=jnp.float32,
            )
            return d * lora_scale[:, None, None]

        def layer_fn(ctx, x, kv_all, lp, li, li_global):
            # ctx: traced arrays shared by every layer. Threaded explicitly
            # (not closed over) so the pp shard_map can pass them through.
            # kv_all: the FULL stacked cache [L, nb, 2, bs, KH*hd]; li is
            # this layer's index into it. The cache is never sliced — the
            # attention kernel takes (cache, layer) and reads only the live
            # pages, and the write is a scatter at layer-offset rows, so the
            # carried buffer updates in place (a per-layer slice/update pair
            # would copy the whole layer cache twice per layer per step).
            flat_write, rope_cos, rope_sin, block_tables, kv_lens, positions = ctx
            h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, offset)
            q = _proj(h, lp, "wq", lp.get("bq"))
            k = _proj(h, lp, "wk", lp.get("bk"))
            v = _proj(h, lp, "wv", lp.get("bv"))
            if has_lora:
                q = q + lora_delta(lp, "wq", h).astype(q.dtype)
                k = k + lora_delta(lp, "wk", h).astype(k.dtype)
                v = v + lora_delta(lp, "wv", h).astype(v.dtype)
            q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
            k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm:  # Qwen3: per-head RMSNorm over hd, pre-rope
                q = _rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
            q = _apply_rope(q, rope_cos, rope_sin)
            k = _apply_rope(k, rope_cos, rope_sin)

            if _decode_write_fused(attn_impl) and T == 1:
                # Decode on the Pallas path: the KV write rides INSIDE the
                # attention kernel (one DMA per sequence before the read
                # loop) — the per-layer XLA scatter below is pure op
                # overhead on the 10 GiB carried buffer at decode shapes.
                from ..ops.paged_attention_pallas import (
                    pallas_paged_attention_decode_write,
                )

                if mesh is not None:
                    raise ValueError(
                        "PST_FUSED_KV_WRITE is a single-device path: the "
                        "fused write kernel has no per-shard wrapper, and "
                        "a Mosaic kernel cannot be partitioned by GSPMD"
                    )

                attn, kv_all = pallas_paged_attention_decode_write(
                    q[:, 0], kv_all, block_tables, kv_lens, li,
                    k.reshape(B, cfg.kv_size), v.reshape(B, cfg.kv_size),
                    flat_write,  # [B*T] == [B] at T==1
                    scale=scale,
                    window=_layer_window(cfg, li_global),
                    softcap=cfg.attn_logit_softcap,
                )
                attn = attn[:, None]
            else:
                # One scatter over the flattened [L*nb*2*bs, KH*hd] row
                # view: slot (blk, pos) of layer li holds its K row at
                # (li*nb + blk)*2*bs + pos and its V row bs rows later. The
                # drop sentinel (flat_write == nb*bs) must map OUT of the
                # whole array, not merely past this layer's rows —
                # past-the-layer would land in layer li+1's first page.
                n_layers_total = kv_all.shape[0]
                blk = flat_write // bs
                pos = flat_write % bs
                oob = n_layers_total * nb * 2 * bs
                idx_k = jnp.where(
                    flat_write >= nb * bs,
                    oob,
                    (li * nb + blk) * (2 * bs) + pos,
                )
                kvd = jnp.concatenate(
                    [
                        k.reshape(B * T, cfg.kv_size),
                        v.reshape(B * T, cfg.kv_size),
                    ],
                    axis=0,
                ).astype(kv_all.dtype)  # [2*B*T, KH*hd]
                idx = jnp.concatenate([idx_k, idx_k + bs])
                kv_all = (
                    kv_all.reshape(n_layers_total * nb * 2 * bs, cfg.kv_size)
                    .at[idx]
                    .set(kvd, mode="drop")
                    .reshape(n_layers_total, nb, 2, bs, cfg.kv_size)
                )

                attn = paged_attention(
                    q, kv_all, block_tables, kv_lens, positions, li,
                    scale=scale, impl=attn_impl,
                    # Window pattern keys off the GLOBAL layer index (under
                    # pp, li is the stage-local cache index).
                    window=_layer_window(cfg, li_global),
                    softcap=cfg.attn_logit_softcap,
                    mesh=mesh,
                )
            attn = attn.reshape(B, T, cfg.q_size).astype(x.dtype)
            o, wo_s = _qdot(attn, lp, "wo")
            if wo_s is not None:
                o = o * wo_s
            if has_lora:
                o = o + lora_delta(lp, "wo", attn)
            o = o.astype(x.dtype)
            if cfg.post_block_norms:  # Gemma-2 post-attention norm
                o = _rms_norm(o, lp["post_attn_norm"], cfg.rms_norm_eps, offset)
            x = x + o

            h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, offset)
            ff = _mlp(cfg, lp, h, moe_impl).astype(x.dtype)
            if cfg.post_block_norms:  # Gemma-2 post-feedforward norm
                ff = _rms_norm(ff, lp["post_mlp_norm"], cfg.rms_norm_eps, offset)
            x = x + ff
            return x, kv_all

        def scan_layers(ctx, x, kv_all, layers, n_layers, li_base=0):
            # The cache rides the scan CARRY — carried while-loop buffers
            # alias across iterations, so peak HBM holds ONE cache. (As scan
            # xs/ys the stacked outputs would be a second full-size
            # allocation: at the 32k-context bench config that is +11 GiB
            # and an instant OOM.) The body never slices the cache; see
            # layer_fn. ``li_base`` is the stage's global layer offset
            # (nonzero under pp, where the scan index is stage-local).
            def body(carry, sl):
                x, kv_all = carry
                lp, i = sl
                x, kv_all = layer_fn(ctx, x, kv_all, lp, i, li_base + i)
                return (x, kv_all), None

            (x, kv_all), _ = jax.lax.scan(
                body, (x, kv_all),
                (layers, jnp.arange(n_layers, dtype=jnp.int32)),
            )
            return x, kv_all

        ctx = (flat_write_real, rope_cos, rope_sin, block_tables, kv_lens,
               positions)
        if pp_size > 1:
            def run_stage(x, repl, scanned_local, gate):
                fw, *rest = repl
                # Suppress cache writes on garbage (rotated) lanes: only the
                # hop where this rank's input is the true composition may
                # write KV; others write to the dropped slot (nb*bs).
                fw = jnp.where(gate, fw, nb * bs)
                layers_local, kv_local = scanned_local
                n_local = cfg.num_layers // pp_size
                x, kv_local = scan_layers(
                    (fw, *rest), x, kv_local, layers_local, n_local,
                    li_base=jax.lax.axis_index(AXIS_PIPELINE) * n_local,
                )
                return x, (layers_local, kv_local)

            x, (_, kv_cache) = pp_compose(
                run_stage, x, ctx, (params["layers"], kv_cache),
                pp_size, mesh,
            )
        else:
            x, kv_cache = scan_layers(
                ctx, x, kv_cache, params["layers"], cfg.num_layers
            )

        x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps, offset)
        head = "lm_head" if "lm_head" in params else "embed"
        unembed = _wcast(params[head], x.dtype)  # [V, D]
        uqs = params.get(head + QUANT_SUFFIX)
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, unembed, preferred_element_type=jnp.float32
            )
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, unembed, preferred_element_type=jnp.float32
            )
        if uqs is not None:
            logits = logits * uqs  # per-vocab-row scale, broadcast over batch
        logits = _softcap(logits, cfg.final_logit_softcap)
        return logits, kv_cache

    def encode(
        self,
        params: Params,
        tokens: jax.Array,
        lengths: jax.Array,
        *,
        pp_size: int = 1,
        sp_size: int = 1,
        moe_impl: str = "auto",
        mesh=None,
    ) -> jax.Array:
        """Embedding path (/v1/embeddings): full causal attention, no cache;
        returns L2-normalized mean-pooled final hidden states [B, D].

        With ``sp_size > 1`` (and ``pp_size == 1``) the per-layer attention
        runs as RING attention over the ``sp`` mesh axis
        (:mod:`production_stack_tpu.ops.ring_attention`): the per-hop KV
        shards across devices and no [B, T, S] score matrix ever
        materializes, so contexts larger than one device's attention memory
        encode across the sp group.
        """
        cfg = self.cfg
        B, T = tokens.shape
        use_ring = sp_size > 1 and mesh is not None
        if use_ring and pp_size > 1:
            raise ValueError("ring (sp) encode does not compose with pp yet")
        if use_ring and (cfg.sliding_window or cfg.attn_logit_softcap):
            raise ValueError(
                "ring (sp) encode does not support sliding-window/"
                "softcap models yet"
            )
        offset = cfg.norm_unit_offset
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        x = _embed_lookup(params, tokens, cfg)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
        rope_cos, rope_sin = _rope_tables(positions, cfg)
        valid = positions < lengths[:, None]  # [B, T]
        if use_ring:
            causal = jnp.zeros((0,), jnp.bool_)  # ring derives its own masks
        else:
            causal = (
                positions[:, None, :] <= positions[:, :, None]
            ) & valid[:, None, :]  # [B, T, S]
        G = cfg.num_heads // cfg.num_kv_heads

        def layer(ctx, x, lp, li):
            rope_cos, rope_sin, causal = ctx
            h = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, offset)
            q = _proj(h, lp, "wq", lp.get("bq")).reshape(
                B, T, cfg.num_kv_heads, G, cfg.head_dim
            )
            k = _proj(h, lp, "wk", lp.get("bk")).reshape(
                B, T, cfg.num_kv_heads, cfg.head_dim
            )
            v = _proj(h, lp, "wv", lp.get("bv")).reshape(
                B, T, cfg.num_kv_heads, cfg.head_dim
            )
            q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
            if cfg.qk_norm:  # Qwen3: per-head RMSNorm over hd, pre-rope
                q = _rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
                k = _rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
            q = _apply_rope(q, rope_cos, rope_sin)
            k = _apply_rope(k, rope_cos, rope_sin)
            if use_ring:
                from ..ops.ring_attention import ring_self_attention

                attn = ring_self_attention(
                    q, k, v, lengths, mesh,
                    scale=cfg.attn_scale,
                ).reshape(B, T, cfg.q_size).astype(x.dtype)
            else:
                qg = q.reshape(B, T, cfg.num_kv_heads, G, cfg.head_dim)
                scores = jnp.einsum(
                    "btkgd,bskd->bkgts", qg, k,
                    preferred_element_type=jnp.float32,
                ) * cfg.attn_scale
                scores = _softcap(scores, cfg.attn_logit_softcap)
                mask = causal
                if cfg.sliding_window:
                    mask = mask & (
                        positions[:, None, :]
                        > positions[:, :, None]
                        - window_eff(_layer_window(cfg, li))
                    )
                scores = jnp.where(mask[:, None, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                attn = jnp.einsum(
                    "bkgts,bskd->btkgd", probs.astype(v.dtype), v,
                    preferred_element_type=jnp.float32,
                ).reshape(B, T, cfg.q_size).astype(x.dtype)
            o, wo_s = _qdot(attn, lp, "wo")
            if wo_s is not None:
                o = o * wo_s
            o = o.astype(x.dtype)
            if cfg.post_block_norms:
                o = _rms_norm(o, lp["post_attn_norm"], cfg.rms_norm_eps, offset)
            x = x + o
            h = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, offset)
            ff = _mlp(cfg, lp, h, moe_impl).astype(x.dtype)
            if cfg.post_block_norms:
                ff = _rms_norm(ff, lp["post_mlp_norm"], cfg.rms_norm_eps, offset)
            x = x + ff
            return x, None

        ctx = (rope_cos, rope_sin, causal)
        if pp_size > 1:
            n_local = cfg.num_layers // pp_size

            def run_stage(x, repl, scanned_local, gate):
                (layers_local,) = scanned_local
                base = jax.lax.axis_index(AXIS_PIPELINE) * n_local
                x, _ = jax.lax.scan(
                    lambda c, s: layer(repl, c, s[0], base + s[1]),
                    x,
                    (layers_local, jnp.arange(n_local, dtype=jnp.int32)),
                )
                return x, (layers_local,)

            x, _ = pp_compose(
                run_stage, x, ctx, (params["layers"],), pp_size, mesh
            )
        else:
            x, _ = jax.lax.scan(
                lambda c, s: layer(ctx, c, s[0], s[1]),
                x,
                (
                    params["layers"],
                    jnp.arange(cfg.num_layers, dtype=jnp.int32),
                ),
            )
        x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps, offset)
        mask = valid[..., None].astype(jnp.float32)
        pooled = (x.astype(jnp.float32) * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-12)


# ----------------------------------------------------------------------------
# Layer primitives
# ----------------------------------------------------------------------------


def _decode_write_fused(attn_impl: str) -> bool:
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
    from ..ops.attention import resolve_attn_impl

    return resolve_attn_impl(attn_impl) == "pallas"


def _rms_norm(
    x: jax.Array, w: jax.Array, eps: float, unit_offset: bool = False
) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    if unit_offset:  # Gemma stores w with effective weight (1 + w), fp32 math
        return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return normed.astype(x.dtype) * w


def _act(cfg: "LlamaConfig"):
    if cfg.hidden_act == "gelu_tanh":  # Gemma GeGLU
        return lambda v: jax.nn.gelu(v, approximate=True)
    if cfg.hidden_act != "silu":
        raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")
    return jax.nn.silu


def _layer_window(cfg: "LlamaConfig", li) -> jax.Array:
    """Sliding window for (traced) layer index ``li``: 0 = global."""
    if not cfg.sliding_window:
        return jnp.int32(0)
    pat = cfg.sliding_window_pattern
    if pat <= 1:
        return jnp.int32(cfg.sliding_window)
    return jnp.where(
        (jnp.asarray(li, jnp.int32) + 1) % pat == 0,
        jnp.int32(0),
        jnp.int32(cfg.sliding_window),
    )


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(logits / cap) * cap if cap else logits


def _embed_lookup(params: Params, tokens: jax.Array, cfg: "LlamaConfig") -> jax.Array:
    """Token embedding gather; int8 tables dequantize with their per-row
    scale (the same rows the tied unembed scales by)."""
    x = params["embed"][tokens]
    s = params.get("embed" + QUANT_SUFFIX)
    if s is not None:
        x = (x.astype(jnp.float32) * s[tokens][..., None]).astype(cfg.jdtype)
    return x


def _mlp(cfg: "LlamaConfig", lp: Params, h: jax.Array, moe_impl: str = "auto") -> jax.Array:
    """SwiGLU MLP block output [B, T, D] in fp32 — dense, or Mixtral-style
    sparse mixture-of-experts when ``cfg.num_experts``."""
    act = _act(cfg)
    if not cfg.num_experts:
        gate = _proj(h, lp, "w_gate")
        up = _proj(h, lp, "w_up")
        ff = (
            act(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        ).astype(h.dtype)
        out, wd_s = _qdot(ff, lp, "w_down")
        if wd_s is not None:
            out = out * wd_s
        return out
    B, T, D = h.shape
    return _moe_mlp(cfg, lp, h.reshape(B * T, D), moe_impl).reshape(B, T, D)


def _moe_mlp(cfg: "LlamaConfig", lp: Params, x: jax.Array, impl: str) -> jax.Array:
    """Sparse MoE SwiGLU over flattened tokens ``x`` [N, D] → fp32 [N, D].

    Router math in fp32 (HF Mixtral convention), top-k weights renormalized.
    Two TPU execution strategies:

    - ``ragged`` — dropless grouped matmul via ``lax.ragged_dot``: token-
      expert pairs are sorted by expert and each expert multiplies exactly
      the tokens routed to it. FLOPs stay proportional to N*k (no capacity
      padding, no token dropping). The idiomatic single-shard / tp-only path.
    - ``dense`` — expert-batched einsums over ALL tokens with a one-hot
      combine. E/k× the FLOPs, but every contraction is a plain einsum that
      GSPMD shards cleanly over the ``ep``/``tp`` mesh axes (experts stay
      resident on their shard; the combine reduction becomes the ep
      all-reduce). Used whenever the expert bank is mesh-sharded.

    ``auto`` resolves to ``ragged`` (the engine passes ``dense`` explicitly
    on ep/tp/pp-sharded meshes — see runner).
    """
    N, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), lp["w_router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E] fp32
    weights, ids = jax.lax.top_k(probs, K)  # [N, K]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if impl not in ("ragged", "dense", "auto"):
        raise ValueError(f"unknown moe_impl {impl!r} (ragged|dense|auto)")

    def deq(key: str) -> jax.Array:
        # ragged_dot has no mixed-dtype story: int8/int4 expert banks
        # dequantize to one transient [E, ., .] bf16 bank (per layer inside
        # the scan — storage stays quantized, only this layer's working copy
        # is bf16). _wmat already dequantizes int4 pre-matmul; int8 hands
        # back its per-channel scale to fold in here.
        w, s = _wmat(lp, key, x.dtype)
        return w if s is None else w * s[:, None, :].astype(x.dtype)

    if impl in ("ragged", "auto"):
        flat_ids = ids.reshape(-1)  # [N*K]
        order = jnp.argsort(flat_ids)  # sorted-by-expert slot order
        tok = order // K  # originating token of each sorted slot
        xs = x[tok]  # [N*K, D]
        group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)
        # A stable name in the device trace for the expert matmuls.
        with jax.named_scope("moe_experts"):
            g = jax.lax.ragged_dot(
                xs, deq("w_gate"), group_sizes,
                preferred_element_type=jnp.float32,
            )
            u = jax.lax.ragged_dot(
                xs, deq("w_up"), group_sizes, preferred_element_type=jnp.float32
            )
            hh = (_act(cfg)(g) * u).astype(x.dtype)
            y = jax.lax.ragged_dot(
                hh, deq("w_down"), group_sizes, preferred_element_type=jnp.float32
            )  # [N*K, D]
        wsort = weights.reshape(-1)[order]  # [N*K]
        return (
            jnp.zeros((N, D), jnp.float32).at[tok].add(y * wsort[:, None])
        )
    # dense: combine[n, e] = summed top-k weight of expert e for token n.
    combine = jnp.sum(
        jax.nn.one_hot(ids, E, dtype=jnp.float32) * weights[..., None], axis=1
    )  # [N, E]
    wg, wg_s = _wmat(lp, "w_gate", x.dtype)
    wu, wu_s = _wmat(lp, "w_up", x.dtype)
    g = jnp.einsum(
        "nd,edf->enf", x, wg, preferred_element_type=jnp.float32
    )
    u = jnp.einsum(
        "nd,edf->enf", x, wu, preferred_element_type=jnp.float32
    )
    if wg_s is not None:
        g = g * wg_s[:, None, :]
    if wu_s is not None:
        u = u * wu_s[:, None, :]
    hh = (_act(cfg)(g) * u).astype(x.dtype)
    wd, wd_s = _wmat(lp, "w_down", x.dtype)
    y = jnp.einsum(
        "enf,efd->end", hh, wd, preferred_element_type=jnp.float32
    )
    if wd_s is not None:
        y = y * wd_s[:, None, :]
    return jnp.einsum("end,ne->nd", y, combine)


def _proj(
    x: jax.Array,
    p: Params,
    name: str,
    b: Optional[jax.Array] = None,
) -> jax.Array:
    out, s = _qdot(x, p, name)
    if s is not None:  # int8 per-output-channel scale
        out = out * s
    if b is not None:
        out = out + b.astype(out.dtype)
    return out.astype(x.dtype)


def _rope_tables(
    positions: jax.Array, cfg: "LlamaConfig"
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables [B, T, hd/2] for the given absolute positions.

    Applies Llama-3.1 "llama3" rope scaling when configured: long-wavelength
    frequencies are divided by ``factor``, short ones kept, with a smooth
    ramp between ``low_freq_factor`` and ``high_freq_factor`` thresholds of
    the original context length (HF ``modeling_rope_utils`` semantics)."""
    half = cfg.head_dim // 2
    freqs = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )  # [half]
    if cfg.rope_scaling_factor:
        wavelen = 2.0 * math.pi / freqs
        low_w = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high_w = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        smooth = (
            cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor
        ) / (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = (
            (1.0 - smooth) * freqs / cfg.rope_scaling_factor + smooth * freqs
        )
        freqs = jnp.where(
            wavelen > low_w,
            freqs / cfg.rope_scaling_factor,  # long wavelengths: full scale
            jnp.where(wavelen < high_w, freqs, scaled),  # short: keep; mid: ramp
        )
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B, T, half]
    return jnp.cos(angles), jnp.sin(angles)


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


# ----------------------------------------------------------------------------
# HF checkpoint loading (local safetensors; zero-egress environment)
# ----------------------------------------------------------------------------

_HF_LAYER_MAP = {
    "self_attn.q_proj": "wq",
    "self_attn.k_proj": "wk",
    "self_attn.v_proj": "wv",
    "self_attn.o_proj": "wo",
    "mlp.gate_proj": "w_gate",
    "mlp.up_proj": "w_up",
    "mlp.down_proj": "w_down",
    "input_layernorm": "attn_norm",
    "post_attention_layernorm": "mlp_norm",
}
_HF_BIAS_MAP = {
    "self_attn.q_proj": "bq",
    "self_attn.k_proj": "bk",
    "self_attn.v_proj": "bv",
}


def _np_quantize(w: np.ndarray, axis: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side (numpy) int8 quantization for checkpoint loading: the bf16
    tree of a big model must never land on the device, and the CPU JAX
    backend may be absent when JAX_PLATFORMS pins the TPU platform."""
    if w.dtype == np.uint16:  # raw bf16 bit pattern from safetensors
        import ml_dtypes

        w = w.view(ml_dtypes.bfloat16)
    wf = w.astype(np.float32)
    amax = np.max(np.abs(wf), axis=axis)
    s = np.maximum(amax, 1e-8) / 127.0
    q = np.clip(np.round(wf / np.expand_dims(s, axis)), -127, 127).astype(np.int8)
    return q, s.astype(np.float32)


def _np_quantize_int4(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side group-wise int4 (contraction axis -2), numpy mirror of
    :func:`quantize_leaf_int4` — bit-identical packing."""
    if w.dtype == np.uint16:
        import ml_dtypes

        w = w.view(ml_dtypes.bfloat16)
    wf = w.astype(np.float32)
    *lead, din, dout = wf.shape
    g = _q4_group(din)
    wg = wf.reshape(*lead, din // g, g, dout)
    amax = np.max(np.abs(wg), axis=-2)
    s = np.maximum(amax, 1e-8) / 7.0
    q = np.clip(np.round(wg / s[..., :, None, :]), -7, 7).astype(np.int8)
    q = q.reshape(*lead, din, dout)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    packed = ((lo & 0x0F) | (hi << 4)).astype(np.int8)
    return packed, s.astype(np.float32)


def load_hf_params(
    cfg: LlamaConfig, model_dir: str, quantize=False
) -> Params:
    """Load HF-format safetensors from a local directory into the pytree.

    HF linear weights are stored ``[out, in]``; ours are ``[in, out]`` so the
    forward is a plain ``x @ w`` (no transposes at serve time). Layers are
    stacked on axis 0 to match the scan layout. ``quantize``: False, or
    "int8"/True (per-channel) or "int4" (group-wise per-layer matmuls,
    embed/lm_head int8) — computed in numpy on the host so the big leaves
    stay host-resident until the runner's sharded device_put.
    """
    qmode = "int8" if quantize is True else quantize
    from safetensors import safe_open

    files = sorted(
        os.path.join(model_dir, f)
        for f in os.listdir(model_dir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")

    d = cfg.jdtype
    L = cfg.num_layers
    layer_acc: Dict[str, list] = {}
    params: Params = {"layers": {}}

    def to_np(t) -> np.ndarray:
        arr = np.asarray(t)
        if arr.dtype == np.dtype("V2"):  # raw bf16 view
            arr = arr.view(np.uint16)
        return arr

    raw: Dict[str, np.ndarray] = {}
    for path in files:
        with safe_open(path, framework="numpy") as f:
            for key in f.keys():
                raw[key] = to_np(f.get_tensor(key))

    def cast(arr: np.ndarray) -> jax.Array:
        if arr.dtype == np.uint16:  # bf16 bit pattern
            return jax.lax.bitcast_convert_type(
                jnp.asarray(arr), jnp.bfloat16
            ).astype(d)
        return jnp.asarray(arr).astype(d)

    def put_top(name: str, arr: np.ndarray) -> None:
        if qmode and name in QUANT_TOP_KEYS:
            q, s = _np_quantize(arr, axis=-1)
            params[name], params[name + QUANT_SUFFIX] = q, s
        else:
            params[name] = cast(arr)

    put_top("embed", raw.pop("model.embed_tokens.weight"))
    params["final_norm"] = cast(raw.pop("model.norm.weight"))
    if "lm_head.weight" in raw:
        put_top("lm_head", raw.pop("lm_head.weight"))

    layer_map = dict(_HF_LAYER_MAP)
    if cfg.qk_norm:
        layer_map["self_attn.q_norm"] = "q_norm"
        layer_map["self_attn.k_norm"] = "k_norm"
    if cfg.post_block_norms:
        # Gemma-2 norm layout: post_attention_layernorm is the POST-attn
        # norm (not the MLP pre-norm as in Llama), and the MLP has its own
        # pre/post pair.
        layer_map["post_attention_layernorm"] = "post_attn_norm"
        layer_map["pre_feedforward_layernorm"] = "mlp_norm"
        layer_map["post_feedforward_layernorm"] = "post_mlp_norm"
    if cfg.num_experts:
        # Mixtral: per-expert w1/w3/w2 (gate/up/down) + the router. Experts
        # are stacked on axis 0 of each layer to form the bank the grouped
        # matmuls consume.
        for hf_name in ("mlp.gate_proj", "mlp.up_proj", "mlp.down_proj"):
            del layer_map[hf_name]
        hf_expert = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
        for ours, wname in hf_expert.items():
            layer_acc[ours] = [
                np.stack(
                    [
                        raw[
                            f"model.layers.{i}.block_sparse_moe.experts."
                            f"{e}.{wname}.weight"
                        ].T
                        for e in range(cfg.num_experts)
                    ],
                    axis=0,
                )
                for i in range(L)
            ]
        layer_acc["w_router"] = [
            raw[f"model.layers.{i}.block_sparse_moe.gate.weight"].T
            for i in range(L)
        ]

    for hf_name, ours in layer_map.items():
        stack = []
        for i in range(L):
            w = raw[f"model.layers.{i}.{hf_name}.weight"]
            if w.ndim == 2:
                w = w.T  # [out,in] -> [in,out]
            stack.append(w)
        layer_acc[ours] = stack
    if cfg.attention_bias:
        for hf_name, ours in _HF_BIAS_MAP.items():
            layer_acc[ours] = [
                raw[f"model.layers.{i}.{hf_name}.bias"] for i in range(L)
            ]

    for name, stack in layer_acc.items():
        stacked = np.stack(stack, axis=0)
        if qmode and name in QUANT_LAYER_KEYS:
            if qmode == "int4":
                q, s = _np_quantize_int4(stacked)
                params["layers"][name] = q
                params["layers"][name + QUANT4_SUFFIX] = s
            else:
                q, s = _np_quantize(stacked, axis=-2)
                params["layers"][name] = q
                params["layers"][name + QUANT_SUFFIX] = s
        else:
            params["layers"][name] = cast(stacked)
    logger.info("loaded %d HF tensors from %s", len(raw) + 3, model_dir)
    return params


def config_from_hf_json(config_path: str, name: str = "") -> LlamaConfig:
    """Build a :class:`LlamaConfig` from an HF ``config.json``."""
    with open(config_path) as f:
        hf = json.load(f)
    mt = hf.get("model_type", "llama")
    if mt not in (
        "llama", "mistral", "qwen2", "qwen3", "mixtral", "gemma", "gemma2",
    ):
        raise ValueError(
            f"unsupported model_type {mt!r} "
            "(llama/mistral/qwen2/qwen3/mixtral/gemma/gemma2)"
        )
    eos = hf.get("eos_token_id", 2)
    eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
    heads = hf["num_attention_heads"]
    gemma = mt in ("gemma", "gemma2")
    act = hf.get("hidden_activation") or hf.get("hidden_act") or "silu"
    act = "gelu_tanh" if act.startswith("gelu") else act
    # Sliding window: Mistral v0.1 (all layers), Gemma-2 (alternating).
    sliding = int(hf.get("sliding_window") or 0)
    if mt not in ("mistral", "gemma2"):
        sliding = 0
    # Llama-3.1-style rope scaling. "linear"/"dynamic" variants are not
    # implemented — refuse loudly rather than serve wrong long-context math.
    rs = hf.get("rope_scaling") or {}
    rs_kind = rs.get("rope_type") or rs.get("type") or ""
    if rs and rs_kind not in ("llama3", "default", ""):
        raise ValueError(
            f"unsupported rope_scaling type {rs_kind!r} (llama3 only)"
        )
    scaling = dict(
        rope_scaling_factor=float(rs.get("factor", 0.0)) if rs_kind == "llama3" else 0.0,
        rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
        rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
        rope_original_max_position=int(
            rs.get("original_max_position_embeddings", 8192)
        ),
    )
    return LlamaConfig(
        **scaling,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim", hf["hidden_size"] // heads),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=hf.get("tie_word_embeddings", gemma),
        attention_bias=mt == "qwen2" or hf.get("attention_bias", False),
        qk_norm=mt == "qwen3",
        num_experts=hf.get("num_local_experts", 0) if mt == "mixtral" else 0,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        hidden_act=act,
        norm_unit_offset=gemma,
        embed_scale=gemma,
        query_pre_attn_scalar=float(hf.get("query_pre_attn_scalar", 0.0))
        if mt == "gemma2" else 0.0,
        attn_logit_softcap=float(hf.get("attn_logit_softcapping") or 0.0)
        if mt == "gemma2" else 0.0,
        final_logit_softcap=float(hf.get("final_logit_softcapping") or 0.0)
        if mt == "gemma2" else 0.0,
        post_block_norms=mt == "gemma2",
        sliding_window=sliding,
        sliding_window_pattern=2 if mt == "gemma2" else 1,
        name=name or hf.get("_name_or_path", mt),
        eos_token_ids=eos_ids,
        bos_token_id=hf.get("bos_token_id"),
    )
