"""The plain reference of ``glm4_moe_lite`` (multi-head latent attention in
every layer; a leading dense SwiGLU layer, then layers of gated experts
behind a ``noaux_tc`` router with one shared expert), written out from the
published equations in ``jax.numpy``: float32, matmul precision "highest",
**expanded attention only** (every head's keys and values rebuilt from the
latents, so the program's absorbed paths are checked against other
mathematics), attention over the whole sequence a head and a block of
queries at a time, the expert layer as a dense loop over the experts the
configuration holds with each token's weight 0 at the experts it did not
choose; no kernels, no cache, no batching. The interface is in
``perf/reference/__init__.py``.

Attention: ``c_q = RMSNorm(W_dq u)``; ``[q_nope_h | q_rope_h] = W_uq c_q``;
``[c_kv | k_r] = W_dkv u``, ``c_kv <- RMSNorm(c_kv)``, ``k_rope = RoPE(k_r)``
(one for all heads), ``q_rope_h <- RoPE(q_rope_h)`` over every rotary
dimension, pairs in halves (the HF rotate-half convention, as the program;
with seeded random weights the other pairing is a fixed permutation of
columns and the same model); ``k_nope_h = W_uk_h c_kv``, ``v_h = W_uv_h
c_kv``; ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) .
k_rope(s)) / sqrt(nope + rope)``, causal softmax, ``out = W_o [o_1 .. o_H]``.
FFN of layer ``i < first_k_dense_replace``: ``W_down (silu(W_gate u) * W_up
u)``. Else: ``s = sigmoid(W_r u)``; the ``top_k`` largest of ``s + b``;
weights ``s`` of the chosen over their sum, times ``routed_scaling_factor``;
``sum_e w_e W_down_e (silu(W_gate_e u) * W_up_e u)`` over the held experts
(``n_routed_experts`` from ``ep_share.first`` on; absent: all) plus the
shared expert.

``gap`` is the smallest distance between the k-th and (k+1)-th of ``s + b``
over the expert layers. The weights stay in the engine's dtype on the device
and are widened a matrix (the expert banks: an expert) at a time; sequences
are padded to ``_pad_len`` (up to 65,536 positions).

Negative controls. Precision, each the nearest below what the configuration
states: ``latent_fp8`` (the cached row, ``c_kv`` after its norm and
``k_rope`` after its rotation, rounded to float8 e4m3's three mantissa
bits), ``weights_fp8`` (every projection and expert matrix so rounded;
router, norms, embedding and head stay). Equations: ``softmax_router``,
``routed_scale_1`` (routed sum not scaled), ``kv_norm_skipped`` (``c_kv``
cached without its norm), ``scale_by_nope_dim`` (``1/sqrt(192)``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import model as shared
from perf.reference import weights as common

VARIANTS = ("none", "latent_fp8", "weights_fp8", "softmax_router",
            "routed_scale_1", "kv_norm_skipped", "scale_by_nope_dim")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo", "w_gate", "w_up",
            "w_down", "w1", "w2", "w_shared_gate", "w_shared_up",
            "w_shared_down")
_HI = jax.lax.Precision.HIGHEST
_Q_BLOCK = 1024  # query rows a block: its scores are [block, T] a head
_MAX_LEN = 65536


def _pad_len(n: int) -> int:
    """Padded at the end, which a causal model never looks at: a few sizes
    below a query block, whole blocks above."""
    for size in (256, 512, _Q_BLOCK):
        if n <= size:
            return size
    size = -(-n // (2 * _Q_BLOCK)) * (2 * _Q_BLOCK)
    if size > _MAX_LEN:
        raise ValueError(f"sequence of {n} tokens is beyond the reference's sizes")
    return size


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _fp8(x):
    """Rounded to three mantissa bits (a convert pair would be folded away
    on the chip); the dtype stays."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)


def _rope(x, cos, sin):
    """x [T, ..., rope]; rotate-half: (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos.reshape(cos.shape[:1] + (1,) * (x.ndim - 2) + cos.shape[1:])
    s = sin.reshape(c.shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(u, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up), w_down)


def weights(cfg):
    from production_stack_tpu.models import registry

    return common.engine_params(
        registry.model_for(configs.program_model_config(cfg)),
        cfg.weights_seed, cfg.flag("--quantization"))


@functools.partial(jax.jit, static_argnames=(
    "rank", "nope", "eps", "scale_dim", "norm_kv", "latent_fp8"))
def attention(x, lw, cos, sin, *, rank, nope, eps, scale_dim, norm_kv,
              latent_fp8):
    """x [T, D] float32 -> the attention block's output [T, D]."""
    T = x.shape[0]
    H = lw["w_uk"].shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    c_q = _rms(_mm(h, lw["w_dq"]), lw["q_norm"], eps)
    q = _mm(c_q, lw["w_uq"]).reshape(T, H, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    ckr = _mm(h, lw["w_dkv"])
    c_kv = ckr[:, :rank]
    if norm_kv:
        c_kv = _rms(c_kv, lw["kv_norm"], eps)
    k_rope = _rope(ckr[:, rank:], cos, sin)
    if latent_fp8:  # what the cache would hold one precision down
        c_kv, k_rope = _fp8(c_kv), _fp8(k_rope)
    qb = min(_Q_BLOCK, T)
    key_pos = jnp.arange(T)

    def head(args):
        q_h, w_uk, w_uv = args  # [T, nope + rope], [nope, rank], [rank, v]
        k = jnp.concatenate([_mm(c_kv, w_uk.T), k_rope], -1)  # [T, nope + rope]
        v = _mm(c_kv, w_uv)

        def block(start):
            rows = jax.lax.dynamic_slice_in_dim(q_h, start, qb)
            s = jnp.einsum("td,sd->ts", rows, k, precision=_HI,
                           preferred_element_type=jnp.float32)
            s = s / math.sqrt(scale_dim)
            q_pos = start + jnp.arange(qb)
            s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, -1), v, precision=_HI,
                              preferred_element_type=jnp.float32)

        return jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, -1)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), lw["w_uk"], lw["w_uv"]))
    return _mm(o.transpose(1, 0, 2).reshape(T, -1), lw["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp(x, norm, lw, *, eps):
    return _swiglu(_rms(x, norm, eps), lw["w_gate"], lw["w_up"], lw["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "scale", "renorm", "eps", "softmax"))
def moe(x, norm, lw, *, top_k, first, scale, renorm, eps, softmax):
    """-> (out [T, D], gap [T])."""
    u = _rms(x, norm, eps)
    logits = _mm(u, lw["w_router"])  # [T, all experts]
    s = jax.nn.softmax(logits, -1) if softmax else jax.nn.sigmoid(logits)
    choice = s + lw["router_bias"]
    ordered = jnp.sort(choice, axis=-1)[:, ::-1]
    gap = ordered[:, top_k - 1] - ordered[:, top_k]
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * scale
    held, _, two_f = lw["w1"].shape
    f = two_f // 2

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)  # [T]
        a = _mm(u, jax.lax.dynamic_index_in_dim(lw["w1"], e, keepdims=False))
        y = _mm(jax.nn.silu(a[:, :f]) * a[:, f:],
                jax.lax.dynamic_index_in_dim(lw["w2"], e, keepdims=False))
        return acc + weight[:, None] * y

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
    shared_out = _swiglu(u, lw["w_shared_gate"], lw["w_shared_up"],
                         lw["w_shared_down"])
    return routed + shared_out, gap


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """One sequence at a time: its hidden states [T, D] are all that is
    held while the layers are walked."""
    hf = cfg.hf
    eps = float(hf.get("rms_norm_eps", 1e-5))
    rank, nope, rope = (hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                        hf["qk_rope_head_dim"])
    n_layers, n_dense = hf["num_hidden_layers"], int(hf.get("first_k_dense_replace", 0))
    share = hf.get("ep_share") or {}
    layers = params["layers"]
    final_norm, lm_head = common.head_weights(params)

    def layer_weights(kind, i):
        lw = {k: v[i] for k, v in layers[kind].items()}
        if variant == "weights_fp8":
            lw = {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}
        return lw

    out = []
    for s in sequences:
        T = _pad_len(len(s["tokens"]))
        ids = np.zeros(T, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        x = common.embed_rows(params, jnp.asarray(ids))
        cos, sin = shared.rope_tables(T, rope, float(hf.get("rope_theta", 10000.0)))
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        gaps = np.full(T, np.inf, np.float32)
        for i in range(n_layers):
            lw = layer_weights("attn", i)
            x = x + attention(
                x, lw, cos, sin, rank=rank, nope=nope, eps=eps,
                scale_dim=nope if variant == "scale_by_nope_dim" else nope + rope,
                norm_kv=variant != "kv_norm_skipped",
                latent_fp8=variant == "latent_fp8")
            if i < n_dense:
                x = x + dense_mlp(x, lw["mlp_norm"], layer_weights("dense", i),
                                  eps=eps)
                continue
            ffn, gap = moe(
                x, lw["mlp_norm"], layer_weights("moe", i - n_dense),
                top_k=hf["num_experts_per_tok"], first=int(share.get("first", 0)),
                scale=1.0 if variant == "routed_scale_1"
                else float(hf.get("routed_scaling_factor", 1.0)),
                renorm=bool(hf.get("norm_topk_prob", True)), eps=eps,
                softmax=variant == "softmax_router")
            x = x + ffn
            gaps = np.minimum(gaps, np.asarray(gap))
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(shared.head_logprobs(x[rows], final_norm, lm_head, eps=eps))
        out.append((lps, gaps[n_prompt - 1: n_prompt - 1 + n_gen]))
        del x
    return out
